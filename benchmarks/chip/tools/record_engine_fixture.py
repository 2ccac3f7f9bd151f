"""Record the small engine trace the phase-reduction test reads.

    python3 benchmarks/chip/tools/record_engine_fixture.py --out <dir>

On one chip: the paged engine over a 2-layer stablelm_3b at full width
(the benchmark's weights, packed, Pallas kernels), warmed, then a few
ticks under the profiler: two requests, one whose prompt takes three
prefill chunks in one tick, each tick in a ``bench.step`` span after a
short ``bench.wait``, all inside a ``bench.window`` span.  Writes
``engine_trace.xplane.pb`` and ``engine_trace.json`` (the ticks, the
dispatches of each program and the phase reduction as recorded) to
``--out``.

The recording is trimmed to what the reductions read (:func:`trim`), which
takes it from ~1.5 MB to ~120 KB: the host's ``serve.*`` and ``bench.*``
spans, each TPU plane's ``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops``
lines without per-event stats, and each operation's name cut to the part
before `` = `` except for the kernels, whose HLO text gives their operands.
The tool refuses to write a trimmed trace whose reduction differs from the
recording's.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [BENCH_DIR, os.path.join(REPO_ROOT, "src")]

LAYERS = 2
ENGINE = {"num_slots": 4, "max_len": 1024, "page_size": 16, "num_pages": 128,
          "prefill_chunk": 256}
PROMPTS = (600, 40)        # three chunks, and one
MAX_NEW = 7                # the prefill token and a decode step each tick
TICKS = 6
WAIT_S = 0.005
KEEP_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
KEEP_SPANS = ("serve.", "bench.")


def _varint(buf: bytes, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _put_varint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _fields(msg: bytes):
    """``(number, encoded field, value)`` of each field of one protobuf
    message: the bytes of a length-delimited field, the integer of a
    varint, None for a fixed-width one."""
    out, i = [], 0
    while i < len(msg):
        start = i
        key, i = _varint(msg, i)
        wire, value = key & 7, None
        if wire == 0:
            value, i = _varint(msg, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(msg, i)
            value, i = msg[i:i + n], i + n
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        out.append((key >> 3, msg[start:i], value))
    return out


def _value(msg: bytes, number: int, default):
    return next((v for f, _, v in _fields(msg) if f == number), default)


def _len_field(number: int, payload: bytes) -> bytes:
    return _put_varint(number << 3 | 2) + _put_varint(len(payload)) + payload


def _metadata_entry(mid: int, name: str) -> bytes:
    """One ``XPlane.event_metadata`` map entry (field 4): key ``mid``, an
    ``XEventMetadata`` holding only its id and name (proto3 leaves 0
    out)."""
    ident = _put_varint(1 << 3) + _put_varint(mid) if mid else b""
    return _len_field(4, ident + _len_field(
        2, ident + _len_field(2, name.encode())))


def _trim_plane(plane: bytes, device: bool) -> bytes:
    """An ``XPlane`` (lines 3, event metadata 4) with only the lines and
    events the reductions read; an ``XEvent``'s metadata id is field 1,
    its stats field 4."""
    from chipbench import tracefile

    names = {}
    for number, _, entry in _fields(plane):
        if number == 4:
            meta = _value(entry, 2, b"")
            names[_value(entry, 1, 0)] = _value(meta, 2, b"").decode()
    out, used = b"", set()
    for number, encoded, value in _fields(plane):
        if number == 4:
            continue
        if number != 3:
            out += encoded
            continue
        if device and _value(value, 2, b"").decode() not in KEEP_LINES:
            continue
        line, kept = b"", 0
        for lnum, lencoded, event in _fields(value):
            if lnum != 4:
                line += lencoded
                continue
            mid = _value(event, 1, 0)
            if not device and not names.get(mid, "").startswith(KEEP_SPANS):
                continue
            used.add(mid)
            kept += 1
            line += (_len_field(4, b"".join(e for f, e, _ in _fields(event)
                                            if f != 4))
                     if device else lencoded)
        if kept:
            out += _len_field(3, line)
    for mid in sorted(used):
        name = names[mid]
        if device and not tracefile.is_kernel(tracefile.op_name(name)):
            name = name.split(" = ", 1)[0]
        out += _metadata_entry(mid, name)
    return out


def trim(raw: bytes) -> bytes:
    """A serialized ``XSpace`` cut to its TPU and host planes (field 1; a
    plane's name is its field 2), each trimmed by :func:`_trim_plane`; the
    recording machine's host names and messages are left out."""
    out = b""
    for number, _, plane in _fields(raw):
        if number != 1:
            continue
        name = _value(plane, 2, b"").decode()
        if name.startswith(("/device:TPU:", "/host:")):
            out += _len_field(1, _trim_plane(
                plane, name.startswith("/device:TPU:")))
    return out


def write_fixture(recorded: str, out_dir: str, meta: dict, peak: dict):
    """Trim the recording into ``out_dir`` and write its meta with the
    phase reduction, refusing a trimmed trace that reduces otherwise."""
    from chipbench import phasetrace

    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "engine_trace.xplane.pb")
    with open(recorded, "rb") as f:
        raw = f.read()
    with open(dst, "wb") as f:
        f.write(trim(raw))
    reduced = phasetrace.reduce(phasetrace.load(dst), peak)
    if reduced != phasetrace.reduce(phasetrace.load(recorded), peak):
        raise RuntimeError("the trimmed trace reduces differently from the "
                           "recording")
    meta = {**meta, "reduced": reduced}
    with open(os.path.join(out_dir, "engine_trace.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta), flush=True)
    print(f"xplane bytes {len(raw)} recorded, {os.path.getsize(dst)} "
          f"trimmed", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import cell as cell_mod
    from chipbench import device, spec, tracefile

    peak = device.check(jax.devices(), 1, device.load_peaks())
    chat = spec.resolve("stablelm_3b.chat")
    cell = dataclasses.replace(
        chat, config={**chat.config, "num_hidden_layers": LAYERS},
        traffic={**chat.traffic, "engine": ENGINE})
    vocab = int(cell.config["vocab_size"])
    _, _, engine = cell_mod.build(cell, 0, cell_mod.Options())
    cell_mod.warm(engine, vocab)

    def dispatched():
        return {c["labels"]["program"]: c["value"]
                for c in engine.metrics.snapshot(meta=False)["counters"]
                if c["name"] == "serve_step_dispatch_total"}

    rng = np.random.default_rng(0)
    for uid, n in enumerate(PROMPTS):
        engine.submit(cell_mod.make_request(
            uid, rng.integers(0, vocab, n, dtype=np.int32), MAX_NEW))
    before = dispatched()
    tmp = tempfile.mkdtemp(prefix="chipbench-fixture-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    time.sleep(0.2)          # let the device tracer start
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(TICKS):
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(WAIT_S)
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
        jax.block_until_ready(engine.state)
    jax.profiler.stop_trace()
    after = dispatched()

    meta = {"layers": LAYERS, "engine": ENGINE, "prompts": list(PROMPTS),
            "max_new": MAX_NEW, "ticks": TICKS,
            "dispatched": {p: after[p] - before.get(p, 0) for p in after},
            "device": device.describe(jax.devices()[:1])}
    try:
        write_fixture(tracefile.find_xplane(tmp), args.out, meta, peak)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
