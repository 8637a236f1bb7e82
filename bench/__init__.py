"""Chip benchmark of the serving engine: one cell (model configuration under
one traffic mix) per run, described by ``BENCHMARK.json`` at the repo root.

Run one cell once from the root of a checkout:

    python3 bench/run.py --workload qwen3-14b.conversation --seed 7 \\
        --seconds 45 --trace 0
"""
