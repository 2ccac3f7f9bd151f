"""The chip benchmark's own code: everything the yardstick needs and the
program under test must not be able to change.

* :mod:`.spec`      — finds a cell's configuration, traffic mix, limits,
  metric readers, layer kind (``layers/<kind>.py``) and kernel cost files
  (``kernels/<family>.py``) by the names in ``BENCHMARK.json`` and its
  files;
* :mod:`.device`    — refuses anything but a TPU listed in ``peaks.json``;
* :mod:`.traffic`   — the one generator every traffic mix file feeds;
* :mod:`.weights`   — the seeded weights, made by the benchmark, not the
  program;
* :mod:`.loop`      — drives ``PagedServeEngine.submit``/``step`` on the
  wall clock and stamps every token;
* :mod:`.stats`     — percentiles and window sums;
* :mod:`.costs`     — operations and bytes of the packed kernels and of a
  served token;
* :mod:`.tracefile` — reduction of a profiler trace to busy time, kernel
  time and idle gaps;
* :mod:`.reference` — the plain float32 model that decides ``correct``,
  each layer by its kind's ``forward``;
* :mod:`.cell`      — one run of one cell, from set-up to the result line.
"""
