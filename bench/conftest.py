"""Fixtures for the benchmark's CPU tests: a checkout of its own that holds
a tiny cell (same program and harness, widths cut so a test can run it)."""
import json
import os
import shutil

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))

TINY_CONFIG = {
    "name": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "program": {"registry": "qwen3-14b",
                "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                              "num_kv_heads": 2, "head_dim": 16,
                              "d_ff": 128, "vocab_size": 512,
                              "norm_eps": 1e-6}},
    "reference": {"qk_norm": True},
    "engine": {"max_batch": 4, "max_seq": 256, "page_size": 16,
               "num_pages": 64, "admit_token_budget": 32},
    "correct": {"min_tokens": 40, "max_requests": 4},
}
TINY_MIX = {"name": "tiny", "prompt": {"median": 40, "sigma": 0.6, "min": 4,
                                       "max": 120},
            "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
            "rate_rps": 6, "lead_in_s": 0.5, "tail_s": 10}
TINY_LIMIT = 0.05


def write_tiny_root(root: str, config: dict = TINY_CONFIG) -> str:
    """A checkout with ``BENCHMARK.json`` naming one cell, ``tiny.tiny``,
    whose configuration (``config``), mix, limits, metric readers and
    references are files of its own; the harness code itself is the
    repository's."""
    b = os.path.join(root, "bench")
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(b, d), exist_ok=True)
    for d in ("metrics", "reference"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(b, d),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), b)
    dump = lambda obj, *p: json.dump(obj, open(os.path.join(b, *p), "w"))
    dump(config, "configs", "tiny.json")
    dump(TINY_MIX, "traffic", "tiny.json")
    dump({"max_logit_gap": {"limit": TINY_LIMIT}}, "cells", "tiny.tiny.json")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.tiny", "config": "tiny",
                       "traffic": "tiny", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "ttft_mean_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock"},
            {"name": "tbt_mean_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "queue_wait_mean_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "serving engine",
             "moves": "ttft_mean_ms"}],
    }
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny_root(str(tmp_path_factory.mktemp("tiny_checkout")))
