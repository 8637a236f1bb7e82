"""Model assembly for the 10 assigned architectures.

One functional forward per family, a unified parameter tree layout, and the
three entry points every downstream layer consumes:

    loss_fn(params, batch)                     -> scalar loss      (train_4k)
    prefill_fn(params, inputs)                 -> (logits, cache)  (prefill_32k)
    decode_fn(params, inputs, cache)           -> (logits, cache)  (decode_32k/long_500k)

Layer stacks are scanned (``lax.scan`` over a leading L dim) so the HLO
stays compact at 60+ layers; remat wraps the scanned body for training.
Families:
  dense   llama3-8b, llama3.2-1b, qwen3-14b, deepseek-7b
  moe     phi3.5-moe (GQA+MoE), deepseek-v2 (MLA+MoE, 2 shared experts)
  ssm     rwkv6 (attention-free, recurrent state)
  hybrid  zamba2 (13 groups: shared-attn block w/ per-group LoRA + 6 Mamba2)
  encdec  seamless-m4t (bidirectional encoder over stubbed audio frames)
  vlm     paligemma (stubbed SigLIP patches as a bidirectional prefix)

Simplifications vs. the exact HF checkpoints (documented in DESIGN.md):
deepseek-v2 uses MoE in *all* layers (real: dense layer 0); zamba2 groups
its 81 layers as 13x(shared attn + 6 mamba) + 3 tail mamba layers.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParallelConfig, shard
from repro.models import layers as Lyr
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rwkv6 as RWKV
from repro.models import mamba2 as M2
from repro.models.layers import rmsnorm, cross_entropy


# =====================================================================
# parameter construction
# =====================================================================
def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)
    p: dict[str, Any] = {"embed": Lyr.embedding_params(ks[0], cfg)}
    L = cfg.num_layers
    d = cfg.d_model

    if cfg.family == "ssm":
        p["layers"] = RWKV.rwkv6_params(ks[1], cfg, num_layers=L)
        p["final_norm"] = jnp.ones((d,), dt)
        return p

    if cfg.family == "hybrid":
        groups, per, tail = _zamba_grouping(cfg)
        p["mamba"] = M2.mamba2_params(ks[1], cfg, num_layers=groups * per)
        if tail:
            p["mamba_tail"] = M2.mamba2_params(ks[2], cfg, num_layers=tail)
        # one shared attention(+mlp) block + per-group LoRA deltas on q/k/v
        shared = {
            "ln1": jnp.ones((d,), dt),
            "attn": Lyr.attention_params(ks[3], cfg),
            "ln2": jnp.ones((d,), dt),
            "mlp": Lyr.mlp_params(ks[4], cfg),
        }
        r = cfg.shared_attn_lora_rank
        kl = jax.random.split(ks[5], 2)
        shared["lora_a"] = Lyr.dense_init(kl[0], (groups, d, r), dt, d)
        shared["lora_b"] = jnp.zeros((groups, r, 3 * d), dt)  # zero-init delta
        p["shared"] = shared
        p["final_norm"] = jnp.ones((d,), dt)
        return p

    # attention trunk families (dense / moe / encdec / vlm)
    trunk = {
        "ln1": jnp.ones((L, d), dt),
        "ln2": jnp.ones((L, d), dt),
    }
    if cfg.use_mla:
        trunk["attn"] = MLA.mla_params(ks[1], cfg, num_layers=L)
    else:
        trunk["attn"] = Lyr.attention_params(ks[1], cfg, num_layers=L)
    if cfg.is_moe:
        trunk["moe"] = MOE.moe_params(ks[2], cfg, num_layers=L)
    else:
        trunk["mlp"] = Lyr.mlp_params(ks[2], cfg, num_layers=L)
    p["layers"] = trunk
    p["final_norm"] = jnp.ones((d,), dt)

    if cfg.family == "encdec":
        Le = cfg.encoder_layers
        p["encoder"] = {
            "ln1": jnp.ones((Le, d), dt),
            "attn": Lyr.attention_params(ks[3], cfg, num_layers=Le),
            "ln2": jnp.ones((Le, d), dt),
            "mlp": Lyr.mlp_params(ks[4], cfg, num_layers=Le),
            "final_norm": jnp.ones((d,), dt),
        }
        p["cross"] = {
            "ln": jnp.ones((L, d), dt),
            "attn": Lyr.attention_params(ks[5], cfg, num_layers=L),
        }
        # audio frontend stub: a projection from precomputed frame features
        p["frontend_proj"] = Lyr.dense_init(ks[6], (d, d), dt, d)
    if cfg.family == "vlm":
        p["frontend_proj"] = Lyr.dense_init(ks[6], (d, d), dt, d)
    return p


def _zamba_grouping(cfg) -> tuple[int, int, int]:
    """(num_groups, mamba_layers_per_group, tail_layers) for the hybrid."""
    per = cfg.shared_attn_every
    groups = cfg.num_layers // per
    tail = cfg.num_layers - groups * per
    return groups, per, tail


# =====================================================================
# attention-trunk forward (dense / moe / encdec / vlm)
# =====================================================================
def _trunk_layer(cfg, parallel, p, x, positions, *, prefix_len=0, cache=None,
                 pos=None, cross=None, enc_out=None, causal=True,
                 table=None, full_seq=0):
    """One decoder layer. Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm({"scale": p["ln1"]}, x, cfg.norm_eps)
    if cfg.use_mla:
        if cache is not None and pos is not None:      # continuation
            if x.shape[1] == 1:
                o, new_cache = MLA.mla_decode(cfg, p["attn"], h, cache, pos)
            else:
                o, new_cache = MLA.mla_extend(cfg, p["attn"], h, cache, pos)
        else:
            o, new_cache = MLA.mla_prefill(cfg, p["attn"], h, positions,
                                           want_cache=cache is not None)
    else:
        o, new_cache = Lyr.attention_block(
            cfg, p["attn"], h, positions=positions, causal=causal,
            prefix_len=prefix_len, cache=cache, pos=pos,
            table=table, full_seq=full_seq)
    x = x + o
    if cross is not None:
        h = rmsnorm({"scale": cross["ln"]}, x, cfg.norm_eps)
        o, _ = Lyr.attention_block(cfg, cross["attn"], h, positions=positions,
                                   causal=False, cross_kv=enc_out)
        x = x + o
    h = rmsnorm({"scale": p["ln2"]}, x, cfg.norm_eps)
    if cfg.is_moe:
        o, aux = MOE.moe_block(cfg, p["moe"], h, parallel)
    else:
        o = Lyr.mlp(p["mlp"], h)
    return x + o, new_cache, aux


def _scan_trunk(cfg, parallel, trunk, x, positions, *, prefix_len=0,
                caches=None, pos=None, cross=None, enc_kv=None, causal=True,
                remat=False, table=None, full_seq=0):
    """Scan the L-stacked trunk. ``caches``/``enc_kv`` carry a leading L dim.

    ``table`` (paged mode) is shared by every layer — the block table maps a
    slot's logical positions to physical pages once, while each layer owns
    its own page pool slice of the scanned cache — so it rides the closure,
    not the scan carry."""
    def body(carry, xs):
        x, aux = carry
        p_l, cache_l, cross_l, enc_l = xs
        x, new_cache, aux_l = _trunk_layer(
            cfg, parallel, p_l, x, positions, prefix_len=prefix_len,
            cache=cache_l, pos=pos, cross=cross_l, enc_out=enc_l,
            causal=causal, table=table, full_seq=full_seq)
        return (x, aux + aux_l), new_cache

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = (trunk, caches, cross, enc_kv)
    (x, aux), new_caches = lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, new_caches, aux


def _encoder_forward(cfg, parallel, p, frames):
    """Bidirectional encoder over (stubbed) frontend embeddings."""
    x = frames @ p["frontend_proj"] if "frontend_proj" in p else frames
    enc = p["encoder"]
    positions = jnp.arange(x.shape[1])

    def body(x, p_l):
        h = rmsnorm({"scale": p_l["ln1"]}, x, cfg.norm_eps)
        o, _ = Lyr.attention_block(cfg, p_l["attn"], h, positions=positions,
                                   causal=False)
        x = x + o
        h = rmsnorm({"scale": p_l["ln2"]}, x, cfg.norm_eps)
        return x + Lyr.mlp(p_l["mlp"], h), None

    x, _ = lax.scan(body, x, {k: enc[k] for k in ("ln1", "attn", "ln2", "mlp")})
    return rmsnorm({"scale": enc["final_norm"]}, x, cfg.norm_eps)


def _cross_kv(cfg, cross_p, enc_out):
    """Precompute per-layer cross-attention K/V from encoder output."""
    B, S, _ = enc_out.shape
    hd, KVH = cfg.resolved_head_dim, cfg.num_kv_heads

    def per_layer(attn_l):
        k = (enc_out @ attn_l["wk"]).reshape(B, S, KVH, hd)
        v = (enc_out @ attn_l["wv"]).reshape(B, S, KVH, hd)
        return k, v

    return jax.vmap(per_layer)(cross_p["attn"])  # [L,B,S,KVH,hd] x2


# =====================================================================
# rwkv6 forward
# =====================================================================
def _rwkv_forward(cfg, p, x, state):
    def body(x, xs):
        p_l, st_l = xs
        return RWKV.rwkv6_block(cfg, p_l, x, st_l)

    x, new_state = lax.scan(body, x, (p["layers"], state))
    return x, new_state


# =====================================================================
# zamba2 (hybrid) forward
# =====================================================================
def _hybrid_forward(cfg, parallel, p, x, positions, *, state, attn_cache=None,
                    pos=None, remat=False):
    """13 groups of (shared attn + 6 mamba) + tail mamba layers.

    state: mamba2 state pytree with leading [groups*per] (+ separate tail);
    attn_cache: {'k','v'} with leading [groups] or None (training w/o cache).
    """
    groups, per, tail = _zamba_grouping(cfg)
    shared = p["shared"]

    def group_body(carry, xs):
        x = carry
        lora_a, lora_b, mamba_g, st_g, cache_g = xs
        # shared attention with per-group LoRA delta folded into q/k/v
        h = rmsnorm({"scale": shared["ln1"]}, x, cfg.norm_eps)
        delta = (h @ lora_a) @ lora_b                     # [B,S,3D]
        dq, dk, dv = jnp.split(delta, 3, axis=-1)
        o, new_cache = Lyr.attention_block(
            cfg, shared["attn"], h, positions=positions, causal=True,
            cache=cache_g, pos=pos, qkv_delta=(dq, dk, dv))
        x = x + o
        h = rmsnorm({"scale": shared["ln2"]}, x, cfg.norm_eps)
        x = x + Lyr.mlp(shared["mlp"], h)

        # inner scan over the group's mamba layers
        def mb(x, xs2):
            p_l, st_l = xs2
            return M2.mamba2_block(cfg, p_l, x, st_l)
        x, new_st = lax.scan(mb, x, (mamba_g, st_g))
        return x, (new_st, new_cache)

    if remat:
        group_body = jax.checkpoint(group_body,
                                    policy=jax.checkpoint_policies.nothing_saveable)

    mamba_grouped = jax.tree.map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), p["mamba"])
    st_grouped = jax.tree.map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), state["body"])
    x, (new_st, new_caches) = lax.scan(
        group_body, x,
        (shared["lora_a"], shared["lora_b"], mamba_grouped, st_grouped,
         attn_cache))
    new_state = {"body": jax.tree.map(
        lambda a: a.reshape(groups * per, *a.shape[2:]), new_st)}
    if tail:
        def mb(x, xs2):
            p_l, st_l = xs2
            return M2.mamba2_block(cfg, p_l, x, st_l)
        x, new_tail = lax.scan(mb, x, (p["mamba_tail"], state["tail"]))
        new_state["tail"] = new_tail
    return x, new_state, new_caches


def hybrid_state_init(cfg, batch: int):
    groups, per, tail = _zamba_grouping(cfg)
    st = {"body": M2.mamba2_state_init(cfg, batch, groups * per)}
    if tail:
        st["tail"] = M2.mamba2_state_init(cfg, batch, tail)
    return st


# =====================================================================
# public entry points
# =====================================================================
def loss_fn(cfg: ModelConfig, parallel: Optional[ParallelConfig], params,
            batch: dict) -> jnp.ndarray:
    """Next-token CE loss. batch: tokens, labels (+frames/patches for stubs)."""
    remat = bool(parallel and parallel.remat)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = Lyr.embed(params["embed"], tokens, cfg)
    x = shard(x, "batch", "seq", None)
    positions = jnp.arange(S)
    aux = jnp.zeros((), jnp.float32)
    prefix_len = 0

    if cfg.family == "ssm":
        state = RWKV.rwkv6_state_init(cfg, B)
        x, _ = _rwkv_forward(cfg, params, x, state)
    elif cfg.family == "hybrid":
        groups, per, tail = _zamba_grouping(cfg)
        state = hybrid_state_init(cfg, B)
        cache0 = _stacked_cache(cfg, groups, B, S, cfg.dtype, train=True)
        x, _, _ = _hybrid_forward(cfg, parallel, params, x, positions,
                                  state=state, attn_cache=cache0, remat=remat)
    elif cfg.family == "encdec":
        enc_out = _encoder_forward(cfg, parallel, params, batch["frames"])
        enc_kv = _cross_kv(cfg, params["cross"], enc_out)
        cross = {"ln": params["cross"]["ln"], "attn": params["cross"]["attn"]}
        x, _, aux = _scan_trunk(cfg, parallel, params["layers"], x, positions,
                                cross=cross, enc_kv=enc_kv, remat=remat)
    else:
        if cfg.family == "vlm":
            patches = batch["patches"] @ params["frontend_proj"]
            x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
            prefix_len = patches.shape[1]
            positions = jnp.arange(x.shape[1])
        x, _, aux = _scan_trunk(cfg, parallel, params["layers"], x, positions,
                                prefix_len=prefix_len, remat=remat)

    x = rmsnorm({"scale": params["final_norm"]}, x, cfg.norm_eps)
    if prefix_len:
        x = x[:, prefix_len:]
    logit = Lyr.logits(params["embed"], x, cfg)
    loss = cross_entropy(logit, batch["labels"], batch.get("loss_mask"))
    return loss + 0.01 * aux


def _stacked_cache(cfg, L, B, S, dtype, train=False):
    hd, KVH = cfg.resolved_head_dim, cfg.num_kv_heads
    if cfg.use_mla:
        return {"ckv": jnp.zeros((L, B, S, cfg.kv_lora_rank), jnp.dtype(dtype)),
                "kr": jnp.zeros((L, B, S, cfg.qk_rope_head_dim), jnp.dtype(dtype))}
    if train:
        # training never reads the cache; attention_block still threads it
        return None
    if str(dtype) == "int8":
        # quantized cache (§Perf H3): per-(token, kv-head) absmax scales;
        # the cache structure itself signals quantization downstream
        # (attention_block checks for the 'k_scale' key).
        return {"k": jnp.zeros((L, B, S, KVH, hd), jnp.int8),
                "k_scale": jnp.zeros((L, B, S, KVH), jnp.float32),
                "v": jnp.zeros((L, B, S, KVH, hd), jnp.int8),
                "v_scale": jnp.zeros((L, B, S, KVH), jnp.float32)}
    return {"k": jnp.zeros((L, B, S, KVH, hd), jnp.dtype(dtype)),
            "v": jnp.zeros((L, B, S, KVH, hd), jnp.dtype(dtype))}


def prefill_fn(cfg: ModelConfig, parallel: Optional[ParallelConfig], params,
               inputs: dict):
    """Prefill: run the full prompt, return (last-token logits, decode cache).

    ``inputs["logits_at"]`` ([B, K] int32, optional) asks for the logits
    at those sequence positions instead, shaped [B, K, V] — one forward
    scores every step of a generated continuation (the reference check of
    a served stream). Positions index the model's sequence, prefix
    embeddings included.
    """
    tokens = inputs["tokens"]
    B, S = tokens.shape
    x = Lyr.embed(params["embed"], tokens, cfg)
    positions = jnp.arange(S)
    prefix_len = 0

    if cfg.family == "ssm":
        state = RWKV.rwkv6_state_init(cfg, B)
        x, new_state = _rwkv_forward(cfg, params, x, state)
        cache = {"state": new_state, "pos": jnp.full((B,), S, jnp.int32)}
    elif cfg.family == "hybrid":
        groups, _, _ = _zamba_grouping(cfg)
        state = hybrid_state_init(cfg, B)
        cache0 = None
        x, new_state, new_caches = _hybrid_forward(
            cfg, parallel, params, x, positions, state=state,
            attn_cache=_prefill_cache_placeholder(cfg, groups), remat=False)
        cache = {"state": new_state, "attn": new_caches,
                 "pos": jnp.full((B,), S, jnp.int32)}
    elif cfg.family == "encdec":
        enc_out = _encoder_forward(cfg, parallel, params, inputs["frames"])
        enc_kv = _cross_kv(cfg, params["cross"], enc_out)
        cross = {"ln": params["cross"]["ln"], "attn": params["cross"]["attn"]}
        cache0 = _prefill_cache_placeholder(cfg, cfg.num_layers)
        x, new_caches, _ = _scan_trunk(cfg, parallel, params["layers"], x,
                                       positions, caches=cache0, cross=cross,
                                       enc_kv=enc_kv)
        cache = {"kv": new_caches, "enc_kv": enc_kv,
                 "pos": jnp.full((B,), S, jnp.int32)}
    else:
        if cfg.family == "vlm":
            patches = inputs["patches"] @ params["frontend_proj"]
            x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
            prefix_len = patches.shape[1]
            positions = jnp.arange(x.shape[1])
        cache0 = _prefill_cache_placeholder(cfg, cfg.num_layers)
        x, new_caches, _ = _scan_trunk(cfg, parallel, params["layers"], x,
                                       positions, prefix_len=prefix_len,
                                       caches=cache0)
        cache = {"kv": new_caches,
                 "pos": jnp.full((B,), x.shape[1], jnp.int32)}

    at = inputs.get("logits_at")
    x = x[:, -1:] if at is None else jnp.take_along_axis(
        x, at[..., None], axis=1)
    x = rmsnorm({"scale": params["final_norm"]}, x, cfg.norm_eps)
    logit = Lyr.logits(params["embed"], x, cfg)
    return (logit[:, 0] if at is None else logit), cache


def _prefill_cache_placeholder(cfg, L):
    """Sentinel telling attention layers to emit their K/V (cache write)."""
    # scan needs a pytree with a leading L dim; zeros of size 0 along seq work
    # as "emit cache" markers: attention_block only checks `cache is not None`
    # and Sq>1 -> writes fresh K/V ignoring the placeholder content.
    if cfg.use_mla:
        return {"ckv": jnp.zeros((L, 0)), "kr": jnp.zeros((L, 0))}
    return {"k": jnp.zeros((L, 0)), "v": jnp.zeros((L, 0))}


def extend_fn(cfg: ModelConfig, parallel: Optional[ParallelConfig], params,
              inputs: dict, cache: dict):
    """Continue a prefill from an existing decode cache with a [B,C] chunk.

    ``cache`` is a fixed-shape decode cache (as built by ``make_decode_cache``
    and populated by a prior prefill/extend/decode); ``cache["pos"]`` [B]
    gives each row's valid length, which may differ per row. The chunk's
    tokens occupy positions pos..pos+C-1: attention families scatter the
    chunk's K/V (or MLA latents) in at those offsets and attend to prefix +
    chunk causally; recurrent families (SSM / hybrid Mamba / RWKV) simply
    advance their carried state, which IS the sequential continuation.
    Returns (last-token logits [B,V], updated cache with pos += C).

    This is what lets the serving engine admit a prompt tail in O(log S)
    compiled calls (descending power-of-2 chunks) instead of up to S serial
    B=1 decodes, while keeping the compile cache bounded: the pools and the
    table width are fixed, so only C and the number of rows B vary (the
    paged engine passes the pow-2 cover of the rows that take a chunk).
    """
    tokens = inputs["tokens"]          # [B, C] int32
    B, C = tokens.shape
    pos = cache["pos"]                 # [B] valid lengths (per-row)
    table = cache.get("table")         # paged mode: [B, p] block table
    span = cache.get("span")           # paged mode: static max_seq marker
    full_seq = span.shape[0] if span is not None else 0
    x = Lyr.embed(params["embed"], tokens, cfg)
    positions = pos[:, None] + jnp.arange(C)[None, :]   # [B, C]

    if cfg.family == "ssm":
        x, new_state = _rwkv_forward(cfg, params, x, cache["state"])
        new_cache = {"state": new_state, "pos": pos + C}
    elif cfg.family == "hybrid":
        x, new_state, new_attn = _hybrid_forward(
            cfg, parallel, params, x, positions, state=cache["state"],
            attn_cache=cache["attn"], pos=pos)
        new_cache = {"state": new_state, "attn": new_attn, "pos": pos + C}
    elif cfg.family == "encdec":
        cross = {"ln": params["cross"]["ln"], "attn": params["cross"]["attn"]}
        x, new_kv, _ = _scan_trunk(cfg, parallel, params["layers"], x,
                                   positions, caches=cache["kv"], pos=pos,
                                   cross=cross, enc_kv=cache["enc_kv"],
                                   table=table, full_seq=full_seq)
        new_cache = {"kv": new_kv, "enc_kv": cache["enc_kv"], "pos": pos + C}
    else:
        # dense / moe / vlm: any prefix (VLM patches, prior prompt chunks)
        # is already in the cache; the chunk itself is text-only.
        x, new_kv, _ = _scan_trunk(cfg, parallel, params["layers"], x,
                                   positions, caches=cache["kv"], pos=pos,
                                   table=table, full_seq=full_seq)
        new_cache = {"kv": new_kv, "pos": pos + C}
    if table is not None:
        new_cache["table"], new_cache["span"] = table, span

    x = rmsnorm({"scale": params["final_norm"]}, x[:, -1:], cfg.norm_eps)
    logit = Lyr.logits(params["embed"], x, cfg)
    return logit[:, 0], new_cache


def decode_fn(cfg: ModelConfig, parallel: Optional[ParallelConfig], params,
              inputs: dict, cache: dict):
    """One decode step: new token against the cache. Returns (logits, cache)."""
    token = inputs["token"]            # [B] int32
    B = token.shape[0]
    pos = cache["pos"]                 # [B] valid lengths
    table = cache.get("table")         # paged mode: [B, p] block table
    span = cache.get("span")           # paged mode: static max_seq marker
    full_seq = span.shape[0] if span is not None else 0
    x = Lyr.embed(params["embed"], token[:, None], cfg)
    positions = pos[:, None]

    if cfg.family == "ssm":
        x, new_state = _rwkv_forward(cfg, params, x, cache["state"])
        new_cache = {"state": new_state, "pos": pos + 1}
    elif cfg.family == "hybrid":
        x, new_state, new_attn = _hybrid_forward(
            cfg, parallel, params, x, positions, state=cache["state"],
            attn_cache=cache["attn"], pos=pos)
        new_cache = {"state": new_state, "attn": new_attn, "pos": pos + 1}
    elif cfg.family == "encdec":
        cross = {"ln": params["cross"]["ln"], "attn": params["cross"]["attn"]}
        x, new_kv, _ = _scan_trunk(cfg, parallel, params["layers"], x,
                                   positions, caches=cache["kv"], pos=pos,
                                   cross=cross, enc_kv=cache["enc_kv"],
                                   table=table, full_seq=full_seq)
        new_cache = {"kv": new_kv, "enc_kv": cache["enc_kv"], "pos": pos + 1}
    else:
        x, new_kv, _ = _scan_trunk(cfg, parallel, params["layers"], x,
                                   positions, caches=cache["kv"], pos=pos,
                                   table=table, full_seq=full_seq)
        new_cache = {"kv": new_kv, "pos": pos + 1}
    if table is not None:
        new_cache["table"], new_cache["span"] = table, span

    x = rmsnorm({"scale": params["final_norm"]}, x, cfg.norm_eps)
    logit = Lyr.logits(params["embed"], x, cfg)
    return logit[:, 0], new_cache


def quantize_decode_cache(cache: dict) -> dict:
    """bf16/f32 GQA decode cache -> int8 + scales (§Perf H3).

    Applies to the ``kv`` part only (MLA latents / SSM states unchanged).
    """
    from repro.models.layers import quantize_kv

    def q_tree(kv):
        # leaves: [L, B, S, KVH, hd] — quantize along hd per (token, head)
        k, v = kv["k"], kv["v"]
        qk, sk = jax.vmap(jax.vmap(quantize_kv, in_axes=1, out_axes=1))(k)
        qv, sv = jax.vmap(jax.vmap(quantize_kv, in_axes=1, out_axes=1))(v)
        return {"k": qk, "k_scale": sk, "v": qv, "v_scale": sv}

    def q_pool(kv):
        # paged pools: [L, P, page, KVH, hd] — quantize_kv is shape-generic
        # over leading dims, so pages quantize exactly like token rows
        qk, sk = quantize_kv(kv["k"])
        qv, sv = quantize_kv(kv["v"])
        return {"k": qk, "k_scale": sk, "v": qv, "v_scale": sv}

    out = dict(cache)
    if "kv" in cache and cache["kv"] is not None and "k" in cache["kv"]:
        out["kv"] = (q_pool if "table" in cache else q_tree)(cache["kv"])
    return out


def make_decode_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Allocate (or spec) the decode-time cache for an arch at a given shape."""
    dtype = dtype or cfg.dtype
    B, S = batch, max_seq
    pos = jnp.zeros((B,), jnp.int32)
    if cfg.family == "ssm":
        return {"state": RWKV.rwkv6_state_init(cfg, B), "pos": pos}
    if cfg.family == "hybrid":
        groups, _, _ = _zamba_grouping(cfg)
        return {"state": hybrid_state_init(cfg, B),
                "attn": _stacked_cache(cfg, groups, B, S, dtype), "pos": pos}
    if cfg.family == "encdec":
        hd, KVH = cfg.resolved_head_dim, cfg.num_kv_heads
        Se = cfg.num_prefix_embeddings
        enc_kv = (jnp.zeros((cfg.num_layers, B, Se, KVH, hd), jnp.dtype(dtype)),
                  jnp.zeros((cfg.num_layers, B, Se, KVH, hd), jnp.dtype(dtype)))
        return {"kv": _stacked_cache(cfg, cfg.num_layers, B, S, dtype),
                "enc_kv": enc_kv, "pos": pos}
    return {"kv": _stacked_cache(cfg, cfg.num_layers, B, S, dtype), "pos": pos}


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """Paged KV applies to the GQA attention-trunk families (dense / moe /
    vlm / encdec self-attention, incl. the int8 cache). MLA latents and the
    recurrent families (ssm / hybrid) carry O(1)-per-token state, not a
    max_seq cache — there is nothing dead to stop attending over, so they
    pass through on the dense layout untouched."""
    return cfg.family not in ("ssm", "hybrid") and not cfg.use_mla


def make_paged_decode_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
                            page_size: int = 16,
                            num_pages: Optional[int] = None, dtype=None):
    """Paged (block-table) decode cache for the GQA attention-trunk families.

    Layout (see ``layers.paged_view``): each layer's K/V leaf is a shared
    pool ``[L, P, page, KVH, hd]`` of ``P = num_pages`` physical pages;
    one block table ``[B, maxP]`` (shared by all layers — every layer
    stores the same logical positions) maps a slot's logical pages to
    physical ones, sentinel ``P`` marking unmapped entries. ``span`` is a
    zero-length-S marker leaf whose *shape* carries the static logical
    max_seq into jit (the paged softmax pads its denominator to it for
    bitwise parity with the dense layout). ``num_pages`` defaults to the
    dense equivalent capacity ``batch * max_seq / page_size``; giving an
    engine the same byte budget but more slots than the dense layout could
    hold is the paged throughput story.
    """
    if page_size & (page_size - 1) or page_size <= 0:
        raise ValueError(f"page_size {page_size} must be a power of two")
    if max_seq % page_size:
        raise ValueError(f"max_seq {max_seq} not a multiple of page_size")
    if not supports_paged_cache(cfg):
        raise ValueError(f"family {cfg.family!r} (use_mla={cfg.use_mla}) "
                         "has no paged layout — use make_decode_cache")
    dtype = dtype or cfg.dtype
    B, L = batch, cfg.num_layers
    hd, KVH = cfg.resolved_head_dim, cfg.num_kv_heads
    maxP = max_seq // page_size
    P = num_pages if num_pages is not None else B * maxP
    if str(dtype) == "int8":
        kv = {"k": jnp.zeros((L, P, page_size, KVH, hd), jnp.int8),
              "k_scale": jnp.zeros((L, P, page_size, KVH), jnp.float32),
              "v": jnp.zeros((L, P, page_size, KVH, hd), jnp.int8),
              "v_scale": jnp.zeros((L, P, page_size, KVH), jnp.float32)}
    else:
        kv = {"k": jnp.zeros((L, P, page_size, KVH, hd), jnp.dtype(dtype)),
              "v": jnp.zeros((L, P, page_size, KVH, hd), jnp.dtype(dtype))}
    cache = {"kv": kv,
             "table": jnp.full((B, maxP), P, jnp.int32),
             "span": jnp.zeros((max_seq,), jnp.int8),
             "pos": jnp.zeros((B,), jnp.int32)}
    if cfg.family == "encdec":
        Se = cfg.num_prefix_embeddings
        cache["enc_kv"] = (
            jnp.zeros((L, B, Se, KVH, hd), jnp.dtype(dtype)),
            jnp.zeros((L, B, Se, KVH, hd), jnp.dtype(dtype)))
    return cache
