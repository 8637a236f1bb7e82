"""One run of one benchmark cell: set-up, an open-loop measured window, a
drain, and the correctness check against the plain reference.

Everything about a cell is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic in
``bench/traffic/<mix>.json``, its limits in ``bench/cells/<cell>.json``,
each metric's reader in ``bench/metrics/<metric>.py`` and the plain
reference the configuration names in ``bench/reference/<module>.py``.
Adding a cell, a configuration, a mix, a metric or a reference adds files
and entries; this file stays.

The timed path is the program's serving engine: ``ServingEngine.step`` with
the paged cache and async admission, over the model built from the
registry entry the configuration names.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from types import ModuleType
from typing import Callable, Optional

import numpy as np

from bench import traffic
from bench import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR_NAME = ".bench_trace"
WARM_RID = 1 << 30          # warm-up request ids, clear of the schedule's


class Refused(SystemExit):
    """The run cannot measure here (no accelerator, too few chips, a device
    missing from the peaks table, a cell that is not defined)."""

    def __init__(self, msg: str):
        print(f"bench: refused: {msg}", file=sys.stderr)
        super().__init__(2)


# ------------------------------------------------------------------- cells
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: traffic.Mix
    limits: dict
    end_to_end: list
    per_layer: list
    reference: ModuleType


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """Resolve ``workload`` through ``BENCHMARK.json`` under ``root``."""
    spec_path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(spec_path):
        raise Refused(f"no BENCHMARK.json in {root}")
    spec = _read_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    bench_dir = os.path.join(root, "bench")
    config = _read_json(os.path.join(root, confs[w["config"]]["file"]))
    mix = traffic.Mix.load(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json"))
    limits = _read_json(os.path.join(bench_dir, "cells", workload + ".json"))
    return Cell(name=workload, chips=int(w["chips"]), config=config, mix=mix,
                limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, workload)],
                reference=load_reference(root, config))


def _load_module(name: str, path: str) -> ModuleType:
    """The Python file at ``path``, executed as a module registered under
    ``name`` (a dataclass in it looks its module up there)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, metric: str) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    return _load_module(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path).read


REFERENCE_NAMES = ("Dims", "make_weights", "logits_at")


def reference_name(config: dict) -> str:
    """The configuration's ``reference.module``; ``gqa`` where it names
    none."""
    return config.get("reference", {}).get("module", "gqa")


def load_reference(root: str, config: dict) -> ModuleType:
    """The plain reference the configuration names, from
    ``bench/reference/<module>.py``, checked for the names of the contract
    in ``bench/reference/__init__.py``."""
    name = reference_name(config)
    path = os.path.join(root, "bench", "reference", f"{name}.py")
    if not name.isidentifier() or not os.path.exists(path):
        raise Refused(f"reference.module {name!r}: no bench/reference/"
                      f"{name}.py in {root}")
    mod = _load_module("bench_reference_" + name, path)
    missing = [n for n in REFERENCE_NAMES if not hasattr(mod, n)]
    if not missing and not callable(getattr(mod.Dims, "from_config", None)):
        missing = ["Dims.from_config"]
    if missing:
        raise Refused(f"reference.module {name!r} lacks {missing}")
    return mod


# ----------------------------------------------------------------- devices
def device_peaks(root: str, kind: str) -> dict:
    table = _read_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_devices(root: str, chips: int):
    """The devices to run on, or a refusal: no accelerator, fewer chips
    than the cell asks for, or a device kind without published peaks."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform == "cpu":
        raise Refused("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips], device_peaks(root, devs[0].device_kind)


def jax_key(seed: int):
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    the low 32 bits)."""
    import jax

    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


# ----------------------------------------------------------- compile count
class CompileLog:
    """Backend compilations (persistent-cache loads included) and their
    program names, as JAX reports them."""

    _instance: Optional["CompileLog"] = None

    def __init__(self):
        self.events: list[tuple[float, str]] = []

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            import jax

            cls._instance = cls()
            inst = cls._instance

            def on_duration(event, duration, **kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    inst.events.append((time.perf_counter(),
                                        str(kw.get("fun_name", "?"))))

            jax.monitoring.register_event_duration_secs_listener(on_duration)
        return cls._instance

    def between(self, a: float, b: float) -> list[str]:
        return [n for t, n in self.events if a <= t < b]


def enable_compile_cache(root: str) -> Optional[str]:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache key). Off on the CPU, where runs are rehearsals."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ----------------------------------------------------------------- program
REQUIRED_FIELDS = ("d_model", "num_layers", "num_heads", "vocab_size",
                   "tie_embeddings", "dtype")

# The published keys of a dense GQA decoder's Hugging Face config.json, for
# a file that names no ``program.published`` map of its own.
GQA_PUBLISHED = {"d_model": "hidden_size", "num_layers": "num_hidden_layers",
                 "num_heads": "num_attention_heads",
                 "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
                 "vocab_size": "vocab_size", "d_ff": "intermediate_size",
                 "tie_embeddings": "tie_word_embeddings",
                 "dtype": "torch_dtype"}

# Published widths and counts: where the file holds one, the map has to
# compare some field with it, so that a map cannot leave a width unchecked.
PUBLISHED_WIDTHS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size",
    "moe_intermediate_size", "vocab_size", "num_local_experts",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
    "v_head_dim", "mamba_d_state", "mamba_headdim", "mamba_expand",
    "mamba_d_conv")


def _published(config: dict, cfg) -> dict:
    """``{ModelConfig field: value the file states}`` through the file's
    ``program.published`` map of ``{field: published key}``, or
    ``GQA_PUBLISHED`` where it has none. The map must name every field of
    ``REQUIRED_FIELDS`` and use every key of ``PUBLISHED_WIDTHS`` that the
    file holds; a field ``ModelConfig`` lacks, or a published key the file
    lacks, is an error that names it."""
    pub = config["program"].get("published", GQA_PUBLISHED)
    fields = {f.name for f in dataclasses.fields(cfg)}
    errors = [f"program.published lacks required field {k!r}"
              for k in REQUIRED_FIELDS if k not in pub]
    errors += [f"program.published compares no field with the published "
               f"width {k!r} that the file holds"
               for k in PUBLISHED_WIDTHS if k in config
               and k not in pub.values()]
    errors += [f"program.published names {k!r}, not a ModelConfig field"
               for k in pub if k not in fields]
    errors += [f"program.published maps {k!r} to {v!r}, not a key of the "
               f"file" for k, v in pub.items() if v not in config]
    if errors:
        raise ValueError("; ".join(errors))
    return {k: config[v] for k, v in pub.items()}


def program_config(config: dict):
    """The registry entry the configuration names, cut by its overrides,
    checked against the widths the configuration file states: every pair
    of its published map."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["registry"]),
                              **prog["overrides"])
    want = _published(config, cfg)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config departs from the file: {bad}")
    return cfg


# --------------------------------------------------------------- open loop
@dataclass
class Rec:
    """One scheduled request and what the harness saw of it."""
    arrival: traffic.Arrival
    req: object = None
    due: float = 0.0                 # host clock
    submit: Optional[float] = None
    left_queue: Optional[float] = None
    stamps: list = field(default_factory=list)
    failed: bool = False


@dataclass
class StepRec:
    t0: float
    t1: float
    decode_rows: int


class OpenLoop:
    """Open loop over a schedule: submit each request when it is due (or
    as soon as the engine returns), step the engine while it has work, and
    stamp every token. TTFT runs from the due time; the first token's time
    is the engine's own ``prefill_done_s``, later tokens are stamped when
    the step that made them returns."""

    def __init__(self, engine, recs: list[Rec], clock=time.perf_counter,
                 spans: bool = False):
        self.engine = engine
        self.recs = recs
        self.clock = clock
        self.next = 0
        self.outstanding: list[Rec] = []
        self.queued: list[Rec] = []
        self.steps: list[StepRec] = []
        self.spans = spans

    def _span(self, name):
        if self.spans:
            import jax

            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    def _submit_due(self, now: float) -> None:
        from repro.serving.engine import Request

        while self.next < len(self.recs) and self.recs[self.next].due <= now:
            r = self.recs[self.next]
            r.req = Request(rid=r.arrival.idx, prompt=r.arrival.prompt,
                            max_new_tokens=r.arrival.max_new_tokens,
                            arrival_s=r.due)
            r.submit = self.clock()
            if self.engine.submit(r.req):
                self.outstanding.append(r)
                self.queued.append(r)
            else:
                r.failed = True
            self.next += 1

    def _step(self) -> None:
        with self._span("step"):
            t0 = self.clock()
            self.engine.step()
            t1 = self.clock()
        with self._span("record"):
            waiting = {id(q) for q in self.engine.waiting}
            still = []
            for r in self.queued:
                if id(r.req) in waiting:
                    still.append(r)
                else:
                    r.left_queue = t0
            self.queued = still
            rows = 0
            keep = []
            for r in self.outstanding:
                n0, n1 = len(r.stamps), len(r.req.tokens)
                for j in range(n0, n1):
                    r.stamps.append(r.req.prefill_done_s if j == 0 else t1)
                rows += max(0, n1 - max(n0, 1))
                if r.req.finish_s is None:
                    keep.append(r)
                elif not r.req.tokens:
                    r.failed = True          # rejected by the engine
            self.outstanding = keep
            self.steps.append(StepRec(t0, t1, rows))

    def run_until(self, t_end: float,
                  stop: Optional[Callable[[], bool]] = None) -> None:
        while True:
            now = self.clock()
            if now >= t_end or (stop is not None and stop()):
                return
            with self._span("submit"):
                self._submit_due(now)
            if self.outstanding:
                self._step()
                continue
            nxt = (self.recs[self.next].due if self.next < len(self.recs)
                   else t_end)
            with self._span("idle"):
                time.sleep(max(0.0, min(nxt, t_end) - self.clock()))


# ----------------------------------------------------------------- warm-up
def _chunks(tail: int, budget: int) -> list[int]:
    """The extend chunks the async arbiter cuts a lone prompt tail into:
    the largest power of two within both the tail and the step budget."""
    out = []
    while tail > 0:
        c = 1 << (min(tail, budget).bit_length() - 1)
        out.append(c)
        tail -= c
    return out


def warm_plan(engine_cfg: dict, arrivals: list) -> list[list[tuple]]:
    """Waves of ``(prompt_len, max_new_tokens)`` requests that reach every
    program shape the traffic can:

    - each prompt bucket (the largest power of two not above a length),
      prefilled alone and, where the admission budget lets several share a
      step, in every power-of-two group up to that. A prompt of exactly
      ``b`` tokens then decodes at the page width of a ``b``-token row, so
      these waves also reach every decode width up to the largest bucket's;
    - the longest prompt with the longest contract's output: its tail is
      cut into extend chunks, and its decode reaches the widest page width;
    - one short wave for each power-of-two extend chunk up to the budget
      that the longest prompt's tail did not reach.

    Each wave is submitted at once and run until the engine is idle."""
    budget = engine_cfg["admit_token_budget"]
    B = engine_cfg["max_batch"]
    lens = sorted({len(a.prompt) for a in arrivals})
    buckets = sorted({1 << (n.bit_length() - 1) for n in lens})
    waves: list[list[tuple]] = []
    for b in buckets:
        n_max = min(B, max(1, budget // b))
        kp = 1
        while True:
            waves.append([(b, 2)] * min(kp, n_max))
            if kp >= n_max:
                break
            kp *= 2
    # the longest prompt, decoding until its row needs the page width of
    # the longest contract
    page, max_p = engine_cfg["page_size"], \
        engine_cfg["max_seq"] // engine_cfg["page_size"]
    width = lambda t: min(1 << (-(-t // page) - 1).bit_length(), max_p)
    longest = max(len(a.prompt) + a.max_new_tokens - 1 for a in arrivals)
    n = lens[-1]
    need = n + 1
    while width(need) < width(longest):
        need = -(-need // page) * page + 1
    waves.append([(n, need - n + 1)])
    seen = set(_chunks(n - (1 << (n.bit_length() - 1)), budget))
    c = 1
    while c <= budget:
        if c not in seen:
            b = next((b for b in buckets if b > c), None)
            if b is not None:
                waves.append([(b + c, 2)])
        c *= 2
    return waves


def warm_up(engine, waves, vocab: int, rng: np.random.Generator) -> int:
    from repro.serving.engine import Request

    rid = WARM_RID
    for wave in waves:
        for n, new in wave:
            engine.submit(Request(rid=rid, max_new_tokens=new,
                                  prompt=rng.integers(0, vocab, n).astype(
                                      np.int32)))
            rid += 1
        while engine.waiting or engine.reconcile()["active"]:
            engine.step()
    return rid - WARM_RID


def warm_small_ops(engine, vocab: int) -> None:
    """The per-admission sampling ops at every row count the engine can
    finalize at once, on the engine's device."""
    import jax
    import jax.numpy as jnp

    from repro.serving.sampling import fold_idx, sample_batch

    dev = engine.device
    B = engine.max_batch
    kp = 1
    shapes = set()
    while kp <= B:
        shapes.add(kp)
        kp *= 2
    shapes.add(B)
    dt = jnp.dtype(engine.cfg.dtype)
    keys = jax.device_put(jax.random.split(jax.random.key(0), B), dev)
    for rows_in in sorted(shapes):
        lg = jax.device_put(jnp.zeros((rows_in, vocab), dt), dev)
        for n in range(1, rows_in + 1):
            rows = jnp.asarray(np.arange(n, dtype=np.int32))
            sl = jnp.asarray(np.arange(n, dtype=np.int32))
            k = fold_idx(keys[sl], jnp.asarray(np.zeros(n, np.int32)))
            t = sample_batch(lg[rows], k, jnp.asarray(np.zeros(n, np.float32)))
            lt = jax.device_put(jnp.zeros((B,), jnp.int32), dev)
            lt.at[sl].set(jnp.asarray(np.asarray(t), jnp.int32))
    jax.block_until_ready(keys)


# --------------------------------------------------------------- reference
def sample_for_check(recs: list[Rec], seed: int, min_tokens: int,
                     max_requests: int) -> list[Rec]:
    """Finished requests due in the window: the one with the most served
    tokens, then others drawn from the seed until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    done = [r for r in recs if r.arrival.segment == "window"
            and r.req is not None and r.req.finish_s is not None
            and r.req.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.arrival.idx)
    longest = max(done, key=lambda r: (len(r.req.tokens), -r.arrival.idx))
    rng = np.random.default_rng([seed, 0xC0FFEE])
    rest = [r for r in done if r is not longest]
    pick = [longest]
    for i in rng.permutation(len(rest)):
        if (sum(len(r.req.tokens) for r in pick) >= min_tokens
                or len(pick) >= max_requests):
            break
        pick.append(rest[int(i)])
    return pick


def logit_gaps(ref: ModuleType, config: dict, seed: int, picks: list[Rec],
               control: Optional[str] = None) -> dict:
    """Widest gap by which a served token's logit from the reference module
    ``ref`` lies below the reference's best, over every served token of
    ``picks``. With ``control`` (``"fp8"``) also ``control_max_logit_gap``:
    the same gap for the token that the reference computed in that
    precision puts first at each of those positions, its tokens in the
    served tokens' place."""
    m = ref.Dims.from_config(config)
    w = ref.make_weights(m, jax_key(seed))
    out = {"max_logit_gap": 0.0, "tokens_checked": 0}
    if control is not None:
        out["control_max_logit_gap"] = 0.0
    for r in picks:
        prompt = np.asarray(r.arrival.prompt)
        served = np.asarray(r.req.tokens, np.int64)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        at = np.arange(len(prompt) - 1, len(seq))
        lg = ref.logits_at(m, w, seq, at)
        best = lg.max(axis=-1)
        gap = best - lg[np.arange(len(at)), served]
        out["max_logit_gap"] = max(out["max_logit_gap"], float(gap.max()))
        out["tokens_checked"] += len(at)
        if control is not None:
            pick = ref.logits_at(m, w, seq, at, quant=control).argmax(-1)
            cg = float((best - lg[np.arange(len(at)), pick]).max())
            out["control_max_logit_gap"] = max(out["control_max_logit_gap"],
                                               cg)
    del w
    return out


# ---------------------------------------------------------------- the run
@dataclass
class RunData:
    """What the metric readers see."""
    cell: Cell
    setup_s: float
    win: tuple                      # host clock (open, close)
    drain_end: float                # host clock when the drain stopped
    recs: list
    steps: list
    peaks: dict
    trace: Optional[dict] = None    # extract of the traced window

    def window_recs(self) -> list:
        return [r for r in self.recs if r.arrival.segment == "window"]

    def ttft_s(self, r: Rec) -> float:
        """From due to first token; a request that got none by the end of
        the drain counts the whole wait it was given."""
        if r.stamps:
            return r.stamps[0] - r.due
        return self.drain_end - r.due

    def token_times(self):
        """(request, index, stamp) of every token stamped in the window."""
        a, b = self.win
        for r in self.recs:
            for j, t in enumerate(r.stamps):
                if a <= t < b:
                    yield r, j, t


def pct(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def start(root: str, workload: str, seed: int, *,
          require_accelerator: bool = True,
          model_hook: Optional[Callable] = None):
    """Cell, devices, peaks, and the engine over freshly made weights."""
    cell = load_cell(root, workload)
    import jax

    if require_accelerator:
        devices, peaks = check_devices(root, cell.chips)
    else:
        devices = jax.devices()[:cell.chips]
        peaks = device_peaks(root, "TPU v5 lite")
    enable_compile_cache(root)
    CompileLog.get()

    from repro.models.api import build
    from repro.serving.engine import ServingEngine

    ecfg = cell.config["engine"]
    model = build(program_config(cell.config))
    if model_hook is not None:
        model = model_hook(model)
    with jax.default_device(devices[0]):
        params = jax.jit(model.init_params)(jax_key(seed))
        jax.block_until_ready(params)
        engine = ServingEngine(
            model, params, max_batch=ecfg["max_batch"],
            max_seq=ecfg["max_seq"], seed=seed & 0x7FFFFFFF,
            admit_mode="async", admit_token_budget=ecfg["admit_token_budget"],
            paged=True, page_size=ecfg["page_size"],
            num_pages=ecfg["num_pages"])
    return cell, devices, peaks, engine


def warm(engine, cell: Cell, arrivals: list, seed: int) -> dict:
    t0 = time.perf_counter()
    vocab = cell.config["vocab_size"]
    n = warm_up(engine, warm_plan(cell.config["engine"], arrivals), vocab,
                np.random.default_rng([seed, 1]))
    t1 = time.perf_counter()
    warm_small_ops(engine, vocab)
    return {"warmup_s": time.perf_counter() - t0, "warmup_requests": n,
            "warmup_small_ops_s": time.perf_counter() - t1}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, process_start: Optional[float] = None,
             require_accelerator: bool = True,
             control: Optional[str] = None, check: bool = True,
             model_hook: Optional[Callable] = None) -> dict:
    """Run ``workload`` once and return the result line's object, with the
    compared numbers under ``checks`` and the run's diagnostics under
    ``_log`` (and its raw records under ``_data``).

    For calibration and the CPU tests only: ``control="fp8"`` runs the
    control, the reference in fp8 with its tokens in the served tokens'
    place at the same positions, through the same comparison, limit and
    ``correct`` (the program's own gap goes to ``_log``);
    ``check=False`` skips the reference; ``require_accelerator=False`` runs on whatever JAX has;
    ``model_hook`` may replace the built model, to plant a fault.
    """
    import jax

    t_start = process_start if process_start is not None else \
        time.perf_counter()
    cell, devices, peaks, engine = start(
        root, workload, seed, require_accelerator=require_accelerator,
        model_hook=model_hook)
    dev = devices[0]
    comp = CompileLog.get()
    arrivals = traffic.schedule(cell.mix, seconds, seed,
                                cell.config["vocab_size"])
    log = {"weights_engine_s": time.perf_counter() - t_start,
           "reference_module": reference_name(cell.config),
           "engine_paged": bool(engine.paged)}
    log.update(warm(engine, cell, arrivals, seed))

    clock = time.perf_counter
    origin = clock() + cell.mix.lead_in_s
    recs = [Rec(a, due=origin + a.due_s) for a in arrivals]
    drv = OpenLoop(engine, recs, clock, spans=trace)
    drv.run_until(origin)                                  # lead-in
    setup_s = clock() - t_start
    trace_dir = os.path.join(root, TRACE_DIR_NAME)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        win_span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        win_span.__enter__()
    w_open = clock()
    close = origin + seconds
    backlog_open = len(drv.outstanding)
    drv.run_until(close)                                   # window
    w_close = clock()
    log["backlog_open_close"] = [backlog_open, len(drv.outstanding)]
    if trace:
        win_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    win_recs = [r for r in recs if r.arrival.segment == "window"]

    def drained() -> bool:
        return all(r.failed or (r.req is not None and r.req.tokens)
                   for r in win_recs)

    drv.run_until(close + cell.mix.tail_s, stop=drained)   # drain
    drain_end = clock()
    for r in win_recs:
        if not (r.req is not None and r.req.tokens):
            r.failed = True
    in_window = comp.between(w_open, w_close)
    log["window_compiles"] = len(in_window)
    log["window_compiled"] = sorted(set(in_window))[:20]
    late = [r.submit - r.due for r in recs
            if r.submit is not None and w_open <= r.due < w_close]
    log["generator_late_p99_ms"] = (pct(late, 99) or 0.0) * 1e3
    log["generator_late_max_ms"] = (max(late) if late else 0.0) * 1e3
    log["engine"] = {k: v for k, v in engine.reconcile().items()
                     if k != "decode_utilization"}

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    data = RunData(cell=cell, setup_s=setup_s,
                   win=(w_open, w_close), drain_end=drain_end, recs=recs,
                   steps=list(drv.steps), peaks=peaks)
    out_breakdown = None
    if trace:
        tr = trace_mod.extract(trace_dir)
        log["trace_planes"] = tr["planes"]
        data.trace = tr
        lo, hi = trace_mod.window(tr)
        devs = tr["devices"][:len(devices)]
        if devs:
            busy = sum(trace_mod.busy_ns(d["ops"], lo, hi) for d in devs)
            device["busy_s"] = busy / len(devs) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            out_breakdown = {"device_ops": trace_mod.top_ops(devs[0], lo, hi),
                             "idle_gaps": trace_mod.top_gaps(tr, devs[0],
                                                             lo, hi)}
            mods: dict = {}
            for name, st, du in devs[0]["modules"]:
                if lo <= st < hi:
                    mods[name] = mods.get(name, 0.0) + du / 1e9
            log["trace_modules"] = sorted(mods.items(),
                                          key=lambda kv: -kv[1])[:12]
        shutil.rmtree(trace_dir, ignore_errors=True)

    ttft = [data.ttft_s(r) for r in data.window_recs()]
    gaps = [t - r.stamps[j - 1] for r, j, t in data.token_times() if j]
    log["window_requests_ttft_p50_p90_ms"] = [
        (pct(ttft, 50) or 0.0) * 1e3, (pct(ttft, 90) or 0.0) * 1e3]
    log["window_gaps_tbt_p50_p99_ms"] = [
        len(gaps), (pct(gaps, 50) or 0.0) * 1e3, (pct(gaps, 99) or 0.0) * 1e3]
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(root, m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # correctness: program state freed first, then the reference
    sample_cfg = cell.config["correct"]
    picks = sample_for_check(recs, seed, sample_cfg["min_tokens"],
                             sample_cfg["max_requests"])
    del engine, drv
    gc.collect()
    log["live_bytes_before_reference"] = sum(
        a.nbytes for a in jax.live_arrays())
    gaps = {"max_logit_gap": math.inf, "tokens_checked": 0}
    t_ref = time.perf_counter()
    if check and picks:
        gaps = logit_gaps(cell.reference, cell.config, seed, picks,
                          control=control)
    log["reference_s"] = time.perf_counter() - t_ref
    log["checked_requests"] = len(picks)
    log["tokens_checked"] = gaps["tokens_checked"]
    gap = gaps["max_logit_gap"]
    if control is not None:
        log["program_max_logit_gap"] = gap
        gap = gaps.get("control_max_logit_gap", math.inf)
    limit = float(cell.limits["max_logit_gap"]["limit"])
    checks = {"max_logit_gap": {"value": gap, "limit": limit}}
    correct = bool(picks) and gap <= limit
    out = {"correct": correct, "attempted": len(win_recs),
           "failed": sum(r.failed for r in win_recs),
           "metrics": metrics, "device": device}
    if out_breakdown is not None:
        out["breakdown"] = out_breakdown
    out["checks"] = checks
    out["_log"] = log
    out["_data"] = data
    return out
