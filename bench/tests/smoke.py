"""Smoke-width cells for the CPU tests: the same families and engine paths
as the chip cells at tiny widths."""
from __future__ import annotations

import copy

from bench import harness

DENSE = {
    "name": "dense-smoke", "family": "dense", "dtype": "float32",
    "model": {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
              "n_kv_heads": 2, "d_ff": 128, "vocab": 256, "act": "silu",
              "glu": True, "tied_embeddings": True, "rope_theta": 10000.0},
    "engine": {"slots": 4, "kv_layout": "paged", "page_size": 16,
               "num_pages": 64, "prefill_chunk": 32,
               "prompt_buckets": [16, 32, 64, 96, 128], "max_seq": 192,
               "decode_kernel": "xla"},
    "limits": {"served_token_gap": 1e-3},
}

MIX = {
    "arrivals": {"kind": "poisson", "rate": 12.0},
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 8,
               "max": 128},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 24},
    "schedule_seed": 1,
    "lead_in_s": 0.5,
    "trace": {"start_s": 0.5, "seconds": 0.8},
}

METRICS_E2E = ["ttft_p75_ms", "itl_p95_ms", "output_tokens_per_s",
               "setup_s"]
METRICS_LAYER = ["decode_roofline", "prefill_roofline", "step_mfu",
                 "step_mfu.ttft",
                 "device_idle_share", "host_step_ms", "batch_occupancy"]


def cell(config: dict, mix: dict = MIX) -> harness.Cell:
    return harness.Cell(name=config["name"], chips=1,
                        config=copy.deepcopy(config), mix=copy.deepcopy(mix),
                        end_to_end=list(METRICS_E2E),
                        per_layer=list(METRICS_LAYER))
