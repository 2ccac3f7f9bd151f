"""A tiny cell for the CPU tests: the stablelm_3b family at ``.reduced()``
widths, served through the same loop, engine and packed kernels (in
interpret mode), with a short open-loop mix."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
for _p in (BENCH_DIR, os.path.join(REPO_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CONFIG = {
    "arch": "stablelm_3b", "num_hidden_layers": 2, "hidden_size": 128,
    "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 512, "rope_theta": 10000,
    "sparsity": "2:16", "groups": {"128": [1, 8], "256": [2, 16]},
    "param_dtype": "float32", "compute_dtype": "bfloat16",
    "rms_norm_eps": 1e-6, "logit_std": 2.0,
}

MIX = {
    "loop": "open", "rate_per_s": 4.0, "preroll_s": 1.0,
    "drain_limit_s": 10.0, "base_seed": 1,
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 4, "max": 60},
    "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                   "min": 12, "max": 40},
    "engine": {"num_slots": 4, "max_len": 112, "page_size": 8,
               "num_pages": 48, "prefill_chunk": 16},
    "check": {"sample_requests": 4, "buckets": [256]},
}

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cell(limit: float):
    from chipbench import spec

    return spec.Cell(name="tiny.chat", chips=1, config_name="tiny",
                     config=CONFIG, traffic_name="tiny", traffic=MIX,
                     limits={"served_logit_gap": {"limit": limit}},
                     end_to_end=[], per_layer=[],
                     layer=spec.layer_of(CONFIG))
