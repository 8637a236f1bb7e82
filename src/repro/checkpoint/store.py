"""Atomic, async, sharded checkpointing with manifest + restart.

Fault-tolerance substrate for 1000+-node posture:

  * **request transcripts** — ``TranscriptSnapshot`` is the serving-side
    checkpoint record: everything needed to resume a preempted in-flight
    request on *another* engine with a bit-identical continuation
    (prompt, generated tokens, and the sampling seed that keys the
    stream). ``save_transcripts``/``load_transcripts`` persist a site's
    drained work atomically (tmp + rename), the same protocol the
    parameter checkpoints use;

  * **atomic** — a checkpoint directory is staged as ``step_N.tmp`` and
    ``os.rename``d into place only after every leaf file and the manifest
    have been fsync'd; readers can never observe a torn checkpoint;
  * **async** — ``save_async`` snapshots device arrays to host (blocking
    only on device→host copy) and writes in a background thread so the
    train loop overlaps I/O with the next steps;
  * **sharded** — each leaf is saved as its own ``.npy`` under a
    tree-path-derived name; at restore time leaves are re-sharded to the
    *current* mesh (elastic re-mesh after a pod/site loss just restores
    with a different ParallelConfig — distributed/elastic.py);
  * **manifest** — JSON with step, leaf paths/shapes/dtypes and a fleet
    config hash; ``latest_step`` scans it for restart;
  * retention — keep the newest ``keep`` checkpoints.

On a real multi-host fleet each host writes its addressable shards and
the manifest is committed by host 0 after a barrier; this container is
single-process so the code path is the degenerate one-host case (the
layout and atomicity protocol are the same).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class TranscriptSnapshot:
    """A preempted request's resumable state — the serving checkpoint.

    Carries the full transcript (prompt + every token generated so far)
    plus the sampling ``seed`` that keys the request's stream. Resuming
    replays the transcript through the prefill-from-cache path and
    continues sampling at token index ``len(tokens)`` under the carried
    seed, so the continuation is bit-identical to the uninterrupted run
    — on *any* engine serving the same model, whatever that engine's own
    seed is. ``attempts`` is the failover retry budget consumed so far.
    """
    rid: int
    prompt: np.ndarray            # [S] int32 token ids
    tokens: list                  # tokens generated before preemption
    max_new_tokens: int
    temperature: float
    seed: int                     # sampling seed that keys this stream
    arrival_s: float = 0.0
    admit_s: Optional[float] = None          # first admission is preserved
    prefill_done_s: Optional[float] = None   # original TTFT is preserved
    attempts: int = 0
    deadline_s: Optional[float] = None

    @classmethod
    def from_request(cls, req: Any, seed: int) -> "TranscriptSnapshot":
        """Snapshot a live ``serving.engine.Request`` (duck-typed)."""
        return cls(rid=int(req.rid),
                   prompt=np.asarray(req.prompt, np.int32),
                   tokens=list(req.tokens),
                   max_new_tokens=int(req.max_new_tokens),
                   temperature=float(req.temperature),
                   seed=int(seed),
                   arrival_s=float(req.arrival_s),
                   admit_s=req.admit_s,
                   prefill_done_s=req.prefill_done_s,
                   attempts=int(req.attempts),
                   deadline_s=req.deadline_s)

    def to_json(self) -> dict:
        return {"rid": int(self.rid),
                "prompt": np.asarray(self.prompt).tolist(),
                "tokens": [int(t) for t in self.tokens],
                "max_new_tokens": int(self.max_new_tokens),
                "temperature": float(self.temperature),
                "seed": int(self.seed),
                "arrival_s": float(self.arrival_s),
                "admit_s": self.admit_s,
                "prefill_done_s": self.prefill_done_s,
                "attempts": int(self.attempts),
                "deadline_s": self.deadline_s}

    @classmethod
    def from_json(cls, d: dict) -> "TranscriptSnapshot":
        return cls(rid=int(d["rid"]),
                   prompt=np.asarray(d["prompt"], np.int32),
                   tokens=[int(t) for t in d["tokens"]],
                   max_new_tokens=int(d["max_new_tokens"]),
                   temperature=float(d["temperature"]),
                   seed=int(d["seed"]),
                   arrival_s=float(d.get("arrival_s", 0.0)),
                   admit_s=d.get("admit_s"),
                   prefill_done_s=d.get("prefill_done_s"),
                   attempts=int(d.get("attempts", 0)),
                   deadline_s=d.get("deadline_s"))


def save_transcripts(path: str, snaps: list, extra: Optional[dict] = None) -> str:
    """Atomically persist a drained site's transcript snapshots."""
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"extra": extra or {},
                   "transcripts": [s.to_json() for s in snaps]}, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def load_transcripts(path: str) -> tuple[list, dict]:
    with open(path) as f:
        d = json.load(f)
    return ([TranscriptSnapshot.from_json(s) for s in d["transcripts"]],
            d.get("extra", {}))


def _flatten_with_names(tree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        name = "_".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path) or "leaf"
        out.append((name, leaf))
    return out, treedef


@dataclass
class CheckpointStore:
    directory: str
    keep: int = 3

    def __post_init__(self):
        os.makedirs(self.directory, exist_ok=True)
        self._pending: Optional[threading.Thread] = None

    # ----------------------------------------------------------- write
    def save(self, step: int, tree: Any, extra: Optional[dict] = None) -> str:
        """Synchronous atomic save. Returns the committed directory."""
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)
        return self._write(step, host_tree, extra or {})

    def save_async(self, step: int, tree: Any,
                   extra: Optional[dict] = None) -> None:
        """Device→host copy now; file I/O in a background thread."""
        self.wait()                                   # one in flight at a time
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)
        t = threading.Thread(target=self._write,
                             args=(step, host_tree, extra or {}), daemon=True)
        t.start()
        self._pending = t

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def _write(self, step: int, host_tree, extra: dict) -> str:
        final = os.path.join(self.directory, f"step_{step:08d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        named, _ = _flatten_with_names(host_tree)
        manifest = {"step": step, "extra": extra, "leaves": []}
        for i, (name, leaf) in enumerate(named):
            fname = f"{i:04d}_{name[:80]}.npy"
            path = os.path.join(tmp, fname)
            arr = np.asarray(leaf)
            true_dtype = str(arr.dtype)
            if arr.dtype.kind == "V" or true_dtype == "bfloat16":
                # ml_dtypes (bf16/fp8) round-trip as raw uint views
                arr = arr.view(f"u{arr.dtype.itemsize}")
            with open(path, "wb") as f:
                np.save(f, arr)
                f.flush()
                os.fsync(f.fileno())
            manifest["leaves"].append(
                {"file": fname, "shape": list(np.shape(leaf)),
                 "dtype": true_dtype})
        mpath = os.path.join(tmp, "manifest.json")
        with open(mpath, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)                         # atomic commit
        self._gc()
        return final

    def _gc(self) -> None:
        steps = self.list_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # ----------------------------------------------------------- read
    def list_steps(self) -> list[int]:
        out = []
        for d in os.listdir(self.directory):
            if d.startswith("step_") and not d.endswith(".tmp"):
                if os.path.exists(os.path.join(self.directory, d,
                                               "manifest.json")):
                    out.append(int(d[5:]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, like: Any, step: Optional[int] = None,
                shardings: Any = None) -> tuple[Any, dict]:
        """Restore into the structure of ``like``. Returns (tree, extra).

        ``shardings``: optional pytree of NamedShardings matching ``like``
        — leaves are device_put onto the *current* mesh (elastic restore).
        """
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        d = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        named, treedef = _flatten_with_names(like)
        if len(named) != len(manifest["leaves"]):
            raise ValueError(
                f"leaf count mismatch: ckpt {len(manifest['leaves'])} vs "
                f"model {len(named)}")
        leaves = []
        for (name, leaf), meta in zip(named, manifest["leaves"]):
            arr = np.load(os.path.join(d, meta["file"]))
            want_dtype = np.asarray(leaf).dtype if hasattr(leaf, "dtype") \
                else arr.dtype
            if arr.dtype.kind == "u" and str(want_dtype) != str(arr.dtype):
                arr = arr.view(want_dtype)        # bf16/fp8 raw-uint round-trip
            if list(arr.shape) != list(np.shape(leaf)):
                raise ValueError(f"shape mismatch for {name}: "
                                 f"{arr.shape} vs {np.shape(leaf)}")
            leaves.append(arr.astype(want_dtype))
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        if shardings is not None:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, s) if s is not None else
                jnp.asarray(x), tree, shardings)
        else:
            tree = jax.tree.map(jnp.asarray, tree)
        return tree, manifest.get("extra", {})
