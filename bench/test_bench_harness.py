"""The harness refuses to measure without a chip or with a device it has no
peaks for, and finds a cell, its configuration, mix, limits and metric
readers from files alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness as H
from bench import traffic
from bench.conftest import write_tiny_root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_the_cpu():
    with pytest.raises(SystemExit) as e:
        H.check_devices(REPO, 1)
    assert e.value.code == 2


def test_refuses_an_unknown_device_kind():
    with pytest.raises(SystemExit):
        H.device_peaks(REPO, "TPU v99 imaginary")
    assert H.device_peaks(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-14b.conversation", "--seed", str(2 ** 31 + 5), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_exits_nonzero_without_a_chip():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_a_cell_loads_from_added_files_alone(tmp_path):
    root = write_tiny_root(str(tmp_path))
    # a metric that exists only as an added file and an added entry
    with open(os.path.join(root, "bench", "metrics", "steps_seen.py"),
              "w") as f:
        f.write("def read(run):\n    return float(len(run.steps))\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving engine",
                              "moves": "tbt_mean_ms",
                              "workloads": ["tiny.tiny"]})
    spec["end_to_end"][1]["workloads"] = ["some.other.cell"]
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = H.load_cell(root, "tiny.tiny")
    assert cell.config["hidden_size"] == 64
    assert cell.mix.rate_rps == 6 and cell.limits["max_logit_gap"]["limit"]
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "tbt_mean_ms"]
    assert [m["name"] for m in cell.per_layer][-1] == "steps_seen"
    read = H.load_reader(root, "steps_seen")
    assert read(type("R", (), {"steps": [1, 2, 3]})()) == 3.0
    with pytest.raises(SystemExit):
        H.load_cell(root, "no.such.cell")


@pytest.mark.parametrize("cell", ["qwen3-14b.conversation"])
def test_committed_cells_resolve(cell):
    c = H.load_cell(REPO, cell)
    assert c.chips == 1 and c.per_layer and len(c.end_to_end) >= 2
    assert c.end_to_end[0]["name"] == "setup_s"
    for m in c.end_to_end + c.per_layer:
        assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("cell", ["qwen3-14b.conversation"])
def test_warm_plan_reaches_every_shape_of_the_traffic(cell):
    c = H.load_cell(REPO, cell)
    e = c.config["engine"]
    page, max_p = e["page_size"], e["max_seq"] // e["page_size"]
    arr = traffic.schedule(c.mix, 51, 2 ** 31 + 77, c.config["vocab_size"])
    flat = [r for w in H.warm_plan(e, arr) for r in w]
    bucket = lambda n: 1 << (n.bit_length() - 1)
    width = lambda t: min(1 << (-(-t // page) - 1).bit_length(), max_p)
    # prefill buckets
    assert {bucket(len(a.prompt)) for a in arr} <= {bucket(n) for n, _ in flat}
    # every power-of-two extend chunk up to the admission budget
    chunks = {x for n, _ in flat
              for x in H._chunks(n - bucket(n), e["admit_token_budget"])}
    assert chunks >= {1 << i for i in range(8)
                      if 1 << i <= e["admit_token_budget"]}
    # every decode page width a row of the traffic can need
    need = {width(t) for a in arr
            for t in range(len(a.prompt) + 1,
                           len(a.prompt) + a.max_new_tokens)}
    got = {width(t) for n, k in flat for t in range(n + 1, n + k)}
    assert need <= got
    assert all(n + k - 1 <= e["max_seq"] for n, k in flat)


def test_extend_chunks_follow_the_arbiter():
    assert H._chunks(4095, 128) == [128] * 31 + [64, 32, 16, 8, 4, 2, 1]
    assert H._chunks(5, 128) == [4, 1]


def test_seeds_above_32_bits_give_distinct_keys():
    import jax

    a = jax.random.key_data(H.jax_key(5))
    b = jax.random.key_data(H.jax_key(5 + 2 ** 32))
    assert (a != b).any()
