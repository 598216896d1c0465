"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

One parameter layout, three execution paths (forward / prefill / decode), all
built on ``jax.lax.scan`` over *stacked* layer parameters — one layer's HLO is
compiled once regardless of depth, which keeps the 40-cell dry-run tractable
and is also the production choice (XLA pipelines scan bodies).

Families:
* dense / vlm — pre-norm GQA attention + (SwiGLU | GELU) MLP.  vlm prepends
  stub patch embeddings to the token embeddings (frontends.py).
* moe — attention + top-k expert layer (models/moe.py), aux loss accumulated
  through the scan carry.
* ssm — Mamba2 SSD blocks (models/ssm.py), attention-free.
* hybrid (zamba2) — mamba backbone; after every ``attn_every`` layers a
  *shared* (weight-tied) attention+MLP block runs on
  ``proj(concat(hidden, embeddings))`` and is added back to the residual
  stream.  Layers are scanned in groups of ``attn_every`` so each shared-block
  application gets its own KV cache slot.

Caches (stacked over layers on axis 0):
* dense/moe/vlm: ``KVCache(k, v)`` with leaves (L, B, S_max, n_kv, hd);
* ssm: ``SsmCache(conv, state)`` with leaves (L, B, ...);
* hybrid: ``{"ssm": SsmCache(L, ...), "attn": KVCache(n_apps, ...)}``.

Residue-resident serving: every execution path here scans *whatever leaves
the parameter tree holds* — prepared trees (models/api.py prepare_params)
swap each stacked ``(L, K, N)`` float weight for a
:class:`~repro.numerics.ResidueTensor` (digit/residue planes + scale as
leaves, moduli/layout/qbits as static metadata), and the same
``jax.lax.scan``s slice them per layer with no change to this module.  The
decode step then performs zero weight quantize/forward-convert work — MoE
expert stacks and the tied-embedding logits matmul included (the
conversion-free steady state the serving engine relies on).

Device scopes: the serving paths (prefill, paged decode and verify, the
logits head) wrap each sub-layer in a ``jax.named_scope`` — ``embed``,
``attn.norm``, ``attn.qkv``, ``attn.rope``, ``attn.core``, ``attn.out``,
``kv.layer``, ``mlp.norm``, ``mlp.gate_up``, ``mlp.down``, ``logits`` (and
``sample`` in the engine) — so each device op's name stack says which
sub-layer made it.  Ops under none of them (the layer scan's own slicing
and stacking) are the scan's plumbing.  Scopes change op metadata only.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import linear, mlp as mlp_mod, moe as moe_mod, ssm as ssm_mod
from repro.models.attention import KVCache
from repro.models.layers import init_embedding, init_rmsnorm, rmsnorm
from repro.models.ssm import Mamba2Dims, SsmCache
from repro.parallel.sharding import constrain, get_shard_ctx


def _sp(x, *roles):
    """SP-only boundary constraint: applied only under ctx.seq_shard (the
    sequence-parallel lever); a no-op otherwise so the baseline layout is
    untouched."""
    ctx = get_shard_ctx()
    if ctx is None or not ctx.seq_shard:
        return x
    return constrain(x, *roles)

__all__ = ["init_lm", "lm_forward", "lm_prefill", "lm_decode",
           "lm_decode_paged", "init_lm_cache", "ssm_dims", "hybrid_groups"]


def ssm_dims(cfg: ArchConfig) -> Mamba2Dims:
    return Mamba2Dims(cfg.d_model, cfg.ssm_state, cfg.ssm_conv,
                      cfg.ssm_expand, cfg.ssm_headdim)


def hybrid_groups(cfg: ArchConfig) -> tuple[int, int]:
    """(n_full_groups, tail_layers) for the hybrid grouped scan."""
    g = cfg.n_layers // cfg.attn_every
    return g, cfg.n_layers - g * cfg.attn_every


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: ArchConfig) -> dict[str, Any]:
    if cfg.family in ("ssm", "hybrid"):
        k1, k2 = jax.random.split(key)
        return {"norm": init_rmsnorm(cfg.d_model),
                "mamba": ssm_mod.init_mamba2(k2, ssm_dims(cfg))}
    k1, k2 = jax.random.split(key)
    p: dict[str, Any] = {
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": attn_mod.init_attention(k1, cfg.d_model, cfg.n_heads,
                                        cfg.n_kv, cfg.hd,
                                        qk_norm=cfg.qk_norm),
        "mlp_norm": init_rmsnorm(cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe(k2, cfg.d_model, cfg.d_ff, cfg.n_experts)
    elif cfg.mlp_type == "gelu":
        p["mlp"] = mlp_mod.init_gelu_mlp(k2, cfg.d_model, cfg.d_ff)
    else:
        p["mlp"] = mlp_mod.init_swiglu(k2, cfg.d_model, cfg.d_ff)
    return p


def _init_shared_block(key: jax.Array, cfg: ArchConfig) -> dict[str, Any]:
    k0, k1, k2 = jax.random.split(key, 3)
    return {
        "in_proj": linear.init_dense(k0, 2 * cfg.d_model, cfg.d_model),
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": attn_mod.init_attention(k1, cfg.d_model, cfg.n_heads,
                                        cfg.n_kv, cfg.hd),
        "mlp_norm": init_rmsnorm(cfg.d_model),
        "mlp": mlp_mod.init_swiglu(k2, cfg.d_model, cfg.d_ff),
    }


def init_lm(key: jax.Array, cfg: ArchConfig) -> dict[str, Any]:
    ke, kl, ks = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys)
    params: dict[str, Any] = {
        "embed": init_embedding(ke, cfg.vocab, cfg.d_model),
        "layers": layers,
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if cfg.family == "hybrid":
        params["shared"] = _init_shared_block(ks, cfg)
    return params


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


@jax.named_scope("embed")
def _embed_inputs(params, cfg: ArchConfig, tokens: jax.Array,
                  patches: jax.Array | None, compute_dtype) -> jax.Array:
    x = params["embed"]["table"].astype(compute_dtype)[tokens]
    if cfg.family == "vlm" and patches is not None:
        x = jnp.concatenate([patches.astype(compute_dtype), x], axis=1)
    return constrain(x, "dp", "seq", None)


@jax.named_scope("logits")
def _logits(params, cfg: ArchConfig, x: jax.Array,
            dense_kw: dict[str, Any] | None = None) -> jax.Array:
    """Logits in compute dtype (softmax/CE upcast to f32 downstream).

    An f32 logits matmul makes the *residual-stream cotangent* f32 for the
    entire backward pass — measured at 40% of granite-20b's HBM traffic
    (EXPERIMENTS.md §Perf iteration 5).

    Under the rns/sdrns systems the tied-embedding logits matmul runs
    through ``linear.dense`` like every other weight matmul: quantized
    per call on unprepared trees, or conversion-free against the
    residue-resident ``embed.logits_w`` :class:`ResidueTensor` that
    ``prepare_params`` encodes from ``table.T`` — so the decode step's
    largest matmul also performs zero weight quantize/forward-convert work.
    """
    dkw = dense_kw or {}
    x = rmsnorm(params["final_norm"], x)
    if dkw.get("system", "bns") in ("rns", "sdrns"):
        w = params["embed"].get("logits_w")
        node = {"w": params["embed"]["table"].astype(jnp.float32).T
                if w is None else w}
        lkw = {k: v for k, v in dkw.items() if k != "out_dtype"}
        logits = linear.dense(node, x, **lkw).astype(x.dtype)
    else:
        logits = jnp.matmul(x, params["embed"]["table"].astype(x.dtype).T,
                            preferred_element_type=x.dtype)
    return constrain(logits, "dp", None, "tp")


def _norm(scope: str, params, x: jax.Array) -> jax.Array:
    """``rmsnorm`` under the device scope ``scope``."""
    with jax.named_scope(scope):
        return rmsnorm(params, x)


# ---------------------------------------------------------------------------
# Per-layer bodies (full-sequence)
# ---------------------------------------------------------------------------


def _dense_layer(lp, x, cfg: ArchConfig, dense_kw, positions):
    # Megatron-SP boundaries (active only under ctx.seq_shard): norms and
    # residual adds run on the seq-sharded stream; activations all-gather
    # right before each matmul block (weights stay TP-sharded) and the
    # row-parallel partial sums reduce-scatter straight back into seq
    # shards.  Without the explicit gather points XLA un-shards the weights
    # instead (EXPERIMENTS.md §Perf iteration 4a, refuted variant).
    hn = rmsnorm(lp["attn_norm"], x)
    hn = _sp(hn, "dp", None, None)             # all-gather seq
    h = attn_mod.attention(
        lp["attn"], hn,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
        qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        positions=positions, dense_kw=dense_kw,
        apply_rope=not cfg.is_encdec,
    )
    h = _sp(h, "dp", "seq", None)              # reduce-scatter wo partials
    x = _sp(x + h, "dp", "seq", None)
    h = rmsnorm(lp["mlp_norm"], x)
    h = _sp(h, "dp", None, None)
    if cfg.family == "moe":
        h, aux = moe_mod.moe(
            lp["moe"], h, n_experts=cfg.n_experts, top_k=cfg.top_k,
            capacity_factor=cfg.moe_cf, dense_kw=dense_kw)
    else:
        fn = mlp_mod.gelu_mlp if cfg.mlp_type == "gelu" else mlp_mod.swiglu
        h, aux = fn(lp["mlp"], h, dense_kw), jnp.float32(0)
    h = _sp(h, "dp", "seq", None)              # reduce-scatter w_down
    return _sp(x + h, "dp", "seq", None), aux


def _ssm_layer(lp, x, cfg: ArchConfig, dense_kw):
    h = ssm_mod.mamba2_forward(lp["mamba"], rmsnorm(lp["norm"], x),
                               ssm_dims(cfg), chunk=cfg.ssm_chunk,
                               dense_kw=dense_kw)
    return x + h


def _shared_block(sp, x, x0, cfg: ArchConfig, dense_kw, positions):
    h = linear.dense(sp["in_proj"], jnp.concatenate([x, x0], axis=-1),
                     **dense_kw)
    a = attn_mod.attention(
        sp["attn"], rmsnorm(sp["attn_norm"], h),
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
        rope_theta=cfg.rope_theta, positions=positions, dense_kw=dense_kw)
    h = h + a
    h = h + mlp_mod.swiglu(sp["mlp"], rmsnorm(sp["mlp_norm"], h), dense_kw)
    return x + h


# ---------------------------------------------------------------------------
# Full-sequence forward (training)
# ---------------------------------------------------------------------------


def lm_forward(
    params: dict[str, Any],
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    patches: jax.Array | None = None,
    dense_kw: dict[str, Any] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """tokens: (B, S_text) -> (logits (B, S, vocab) f32, aux_loss scalar).

    For vlm, ``patches`` (B, n_img, d) are prepended: S = n_img + S_text.
    """
    dense_kw = dense_kw or {}
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed_inputs(params, cfg, tokens, patches, compute_dtype)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp):
            x, aux = carry
            x, a = _dense_layer(lp, x, cfg, dense_kw, positions)
            return (x, aux + a), None

        body = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                                   params["layers"])
    elif cfg.family == "ssm":
        def body(x, lp):
            return _ssm_layer(lp, x, cfg, dense_kw), None

        body = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = jnp.float32(0)
    elif cfg.family == "hybrid":
        x0 = x
        G, tail = hybrid_groups(cfg)
        grouped = jax.tree_util.tree_map(
            lambda a: a[: G * cfg.attn_every].reshape(
                G, cfg.attn_every, *a.shape[1:]),
            params["layers"])
        tail_p = jax.tree_util.tree_map(lambda a: a[G * cfg.attn_every:],
                                        params["layers"])

        def mamba_body(x, lp):
            return _ssm_layer(lp, x, cfg, dense_kw), None

        mb = jax.checkpoint(mamba_body) if cfg.remat else mamba_body

        def group_body(x, glp):
            x, _ = jax.lax.scan(mb, x, glp)
            x = _shared_block(params["shared"], x, x0, cfg, dense_kw,
                              positions)
            return x, None

        gb = jax.checkpoint(group_body) if cfg.remat else group_body
        x, _ = jax.lax.scan(gb, x, grouped)
        if tail:
            x, _ = jax.lax.scan(mb, x, tail_p)
        aux = jnp.float32(0)
    else:
        raise ValueError(f"lm_forward does not handle family {cfg.family!r}")

    return _logits(params, cfg, x, dense_kw), aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_lm_cache(cfg: ArchConfig, batch: int, s_max: int,
                  dtype=jnp.bfloat16):
    L = cfg.n_layers
    if cfg.family in ("dense", "moe", "vlm"):
        shape = (L, batch, s_max, cfg.n_kv, cfg.hd)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    dims = ssm_dims(cfg)
    ssm_cache = SsmCache(
        jnp.zeros((L, batch, dims.d_conv - 1, dims.conv_dim), jnp.float32),
        jnp.zeros((L, batch, dims.n_heads, dims.headdim, dims.d_state),
                  jnp.float32),
    )
    if cfg.family == "ssm":
        return ssm_cache
    G, _ = hybrid_groups(cfg)
    shape = (G, batch, s_max, cfg.n_kv, cfg.hd)
    return {"ssm": ssm_cache,
            "attn": KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def lm_prefill(
    params: dict[str, Any],
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    s_max: int | None = None,
    patches: jax.Array | None = None,
    dense_kw: dict[str, Any] | None = None,
    cache_dtype=jnp.bfloat16,
    logits_at: jax.Array | None = None,
) -> tuple[jax.Array, Any]:
    """Process the prompt and *produce* the cache (padded to ``s_max``).

    The cache is built from the layer scan's stacked outputs — no
    zero-initialized cache argument, so exactly one cache buffer is ever
    live (the xs/ys double-buffer dominated the 32k/500k cells' memory).

    ``logits_at``: optional (B,) int32 *runtime* positions to read logits
    from instead of the last row — the paged serving path right-pads ragged
    prompts (causal attention keeps prefix rows exact regardless of the
    padded tail, so page contents stay a pure function of the token prefix)
    and gathers each request's logits at ``plen - 1``.
    """
    dense_kw = dense_kw or {}
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed_inputs(params, cfg, tokens, patches, compute_dtype)
    S = x.shape[1]
    if s_max is None:
        s_max = S
    akw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
               dense_kw=dense_kw, apply_rope=not cfg.is_encdec)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, lp):
            h, c2 = attn_mod.prefill_attention(
                lp["attn"], _norm("attn.norm", lp["attn_norm"], x), s_max,
                cache_dtype=cache_dtype, **akw)
            x = x + h
            h = _norm("mlp.norm", lp["mlp_norm"], x)
            if cfg.family == "moe":
                h, _ = moe_mod.moe(lp["moe"], h, n_experts=cfg.n_experts,
                                   top_k=cfg.top_k,
                                   capacity_factor=cfg.moe_cf,
                                   dense_kw=dense_kw)
            else:
                fn = (mlp_mod.gelu_mlp if cfg.mlp_type == "gelu"
                      else mlp_mod.swiglu)
                h = fn(lp["mlp"], h, dense_kw)
            return x + h, c2

        x, new_cache = jax.lax.scan(body, x, params["layers"])
    elif cfg.family == "ssm":
        def body(x, lp):
            h, c2 = ssm_mod.mamba2_forward(
                lp["mamba"], rmsnorm(lp["norm"], x), ssm_dims(cfg),
                chunk=cfg.ssm_chunk, dense_kw=dense_kw, return_cache=True)
            return x + h, c2

        x, new_cache = jax.lax.scan(body, x, params["layers"])
    elif cfg.family == "hybrid":
        x0 = x
        G, tail = hybrid_groups(cfg)
        ae = cfg.attn_every
        grouped = jax.tree_util.tree_map(
            lambda a: a[: G * ae].reshape(G, ae, *a.shape[1:]),
            params["layers"])
        tail_p = jax.tree_util.tree_map(lambda a: a[G * ae:],
                                        params["layers"])
        skw = dict(akw)
        skw.pop("qk_norm")

        def mamba_body(x, lp):
            h, c2 = ssm_mod.mamba2_forward(
                lp["mamba"], rmsnorm(lp["norm"], x), ssm_dims(cfg),
                chunk=cfg.ssm_chunk, dense_kw=dense_kw, return_cache=True)
            return x + h, c2

        def group_body(x, glp):
            x, gc2 = jax.lax.scan(mamba_body, x, glp)
            sp = params["shared"]
            h = linear.dense(sp["in_proj"],
                             jnp.concatenate([x, x0], axis=-1), **dense_kw)
            a, ac2 = attn_mod.prefill_attention(
                sp["attn"], rmsnorm(sp["attn_norm"], h), s_max,
                cache_dtype=cache_dtype, **skw)
            h = h + a
            h = h + mlp_mod.swiglu(sp["mlp"], rmsnorm(sp["mlp_norm"], h),
                                   dense_kw)
            return x + h, (gc2, ac2)

        x, (gs2, attn2) = jax.lax.scan(group_body, x, grouped)
        ssm2 = jax.tree_util.tree_map(
            lambda a: a.reshape(G * ae, *a.shape[2:]), gs2)
        if tail:
            x, tail2 = jax.lax.scan(mamba_body, x, tail_p)
            ssm2 = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0), ssm2, tail2)
        new_cache = {"ssm": ssm2, "attn": attn2}
    else:
        raise ValueError(cfg.family)

    if logits_at is not None:
        B = x.shape[0]
        xg = x[jnp.arange(B), jnp.asarray(logits_at, jnp.int32)][:, None]
        logits = _logits(params, cfg, xg, dense_kw)
    else:
        logits = _logits(params, cfg, x[:, -1:], dense_kw)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Decode (one token)
# ---------------------------------------------------------------------------


def lm_decode(
    params: dict[str, Any],
    cfg: ArchConfig,
    token: jax.Array,
    cache,
    pos: jax.Array,
    *,
    dense_kw: dict[str, Any] | None = None,
) -> tuple[jax.Array, Any]:
    """token: (B, 1) int32; pos: scalar int32 -> (logits (B, vocab), cache).

    KV caches ride through the layer scan as *carry* and are updated with
    ``dynamic_update_index_in_dim`` — XLA performs the update in place on
    the donated buffer, so one cache copy is live instead of the xs/ys two
    (decisive at decode_32k/long_500k sizes).  The small SSM states stay as
    xs/ys for simplicity.
    """
    dense_kw = dense_kw or {}
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = params["embed"]["table"].astype(compute_dtype)[token]  # (B, 1, d)
    x = constrain(x, "dp", None, None)
    akw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
               dense_kw=dense_kw, apply_rope=not cfg.is_encdec)

    def idx(arr, i):
        return jax.lax.dynamic_index_in_dim(arr, i, 0, keepdims=False)

    def upd(arr, val, i):
        return jax.lax.dynamic_update_index_in_dim(
            arr, val.astype(arr.dtype), i, 0)

    if cfg.family in ("dense", "moe", "vlm"):
        L = cfg.n_layers

        def body(carry, inp):
            x, ck, cv = carry
            i, lp = inp
            layer_c = KVCache(idx(ck, i), idx(cv, i))
            h, c2 = attn_mod.decode_attention(
                lp["attn"], rmsnorm(lp["attn_norm"], x), layer_c, pos,
                **akw)
            ck, cv = upd(ck, c2.k, i), upd(cv, c2.v, i)
            x = x + h
            h = rmsnorm(lp["mlp_norm"], x)
            if cfg.family == "moe":
                h, _ = moe_mod.moe(lp["moe"], h, n_experts=cfg.n_experts,
                                   top_k=cfg.top_k,
                                   capacity_factor=cfg.moe_cf,
                                   dense_kw=dense_kw)
            else:
                fn = (mlp_mod.gelu_mlp if cfg.mlp_type == "gelu"
                      else mlp_mod.swiglu)
                h = fn(lp["mlp"], h, dense_kw)
            return (x + h, ck, cv), None

        (x, ck, cv), _ = jax.lax.scan(
            body, (x, cache.k, cache.v),
            (jnp.arange(L, dtype=jnp.int32), params["layers"]))
        new_cache = KVCache(ck, cv)
    elif cfg.family == "ssm":
        def body(x, inp):
            lp, c = inp
            h, c2 = ssm_mod.mamba2_decode(lp["mamba"],
                                          rmsnorm(lp["norm"], x), c,
                                          ssm_dims(cfg), dense_kw=dense_kw)
            return x + h, c2

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    elif cfg.family == "hybrid":
        x0 = x
        G, tail = hybrid_groups(cfg)
        ae = cfg.attn_every
        grouped = jax.tree_util.tree_map(
            lambda a: a[: G * ae].reshape(G, ae, *a.shape[1:]),
            params["layers"])
        tail_p = jax.tree_util.tree_map(lambda a: a[G * ae:],
                                        params["layers"])
        ssm_cache, attn_cache = cache["ssm"], cache["attn"]
        gs_cache = jax.tree_util.tree_map(
            lambda a: a[: G * ae].reshape(G, ae, *a.shape[1:]), ssm_cache)
        tail_cache = jax.tree_util.tree_map(lambda a: a[G * ae:], ssm_cache)
        skw = dict(akw)
        skw.pop("qk_norm")

        def mamba_body(x, inp):
            lp, c = inp
            h, c2 = ssm_mod.mamba2_decode(lp["mamba"],
                                          rmsnorm(lp["norm"], x), c,
                                          ssm_dims(cfg), dense_kw=dense_kw)
            return x + h, c2

        def group_body(carry, inp):
            x, ak, av = carry
            g, glp, gc = inp
            x, gc2 = jax.lax.scan(mamba_body, x, (glp, gc))
            sp = params["shared"]
            h = linear.dense(sp["in_proj"],
                             jnp.concatenate([x, x0], axis=-1), **dense_kw)
            app_c = KVCache(idx(ak, g), idx(av, g))
            a, c2 = attn_mod.decode_attention(
                sp["attn"], rmsnorm(sp["attn_norm"], h), app_c, pos, **skw)
            ak, av = upd(ak, c2.k, g), upd(av, c2.v, g)
            h = h + a
            h = h + mlp_mod.swiglu(sp["mlp"], rmsnorm(sp["mlp_norm"], h),
                                   dense_kw)
            return (x + h, ak, av), gc2

        (x, ak, av), gs2 = jax.lax.scan(
            group_body, (x, attn_cache.k, attn_cache.v),
            (jnp.arange(G, dtype=jnp.int32), grouped, gs_cache))
        ssm2 = jax.tree_util.tree_map(
            lambda a: a.reshape(G * ae, *a.shape[2:]), gs2)
        if tail:
            x, tail2 = jax.lax.scan(mamba_body, x, (tail_p, tail_cache))
            ssm2 = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0), ssm2, tail2)
        new_cache = {"ssm": ssm2, "attn": KVCache(ak, av)}
    else:
        raise ValueError(cfg.family)

    logits = _logits(params, cfg, x, dense_kw)
    return logits[:, 0], new_cache


def lm_decode_paged(
    params: dict[str, Any],
    cfg: ArchConfig,
    token: jax.Array,
    kv,
    block_tab: jax.Array,
    pos: jax.Array,
    *,
    page_size: int,
    dense_kw: dict[str, Any] | None = None,
    cache_dtype=jnp.bfloat16,
    with_syndrome: bool = False,
):
    """One decode step against the *paged* KV pool (dense/moe/vlm families).

    token: (B, 1) int32;  kv: :class:`~repro.numerics.kv_pages.PagedKV` with
    leaves stacked over layers;  block_tab: (B, n_pmax) int32 page lists;
    pos: **(B,) int32 per-slot positions** — continuous batching decodes
    every slot at its own depth in one dispatch.  Returns
    ``(logits (B, vocab), kv)``.  The pool rides the layer scan as carry
    exactly like the dense cache (in-place update on the donated buffer);
    ResidueTensor pools carry their planes+scale leaves through the same
    scan untouched.

    ``with_syndrome=True`` (redundant residue pools) stacks each layer's
    in-kernel KV syndrome count off the scan: returns ``(logits, kv,
    syn (B, L) int32)`` — the per-(slot, layer) fault map the serving
    engine's escalation policy consumes.
    """
    from repro.numerics import kv_pages as kvp

    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"paged decode supports dense/moe/vlm, not {cfg.family!r}")
    dense_kw = dense_kw or {}
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = params["embed"]["table"].astype(compute_dtype)[token]  # (B,1,d)
        x = constrain(x, "dp", None, None)
    akw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
               dense_kw=dense_kw, apply_rope=not cfg.is_encdec)
    L = cfg.n_layers

    def body(carry, inp):
        x, kv = carry
        i, lp = inp
        with jax.named_scope("kv.layer"):
            lay = kvp.layer_slice(kv, i)
        att = attn_mod.paged_decode_attention(
            lp["attn"], _norm("attn.norm", lp["attn_norm"], x), lay,
            block_tab, pos, page_size=page_size, cache_dtype=cache_dtype,
            with_syndrome=with_syndrome, **akw)
        if with_syndrome:
            h, lay2, syn = att
        else:
            (h, lay2), syn = att, None
        with jax.named_scope("kv.layer"):
            kv = kvp.layer_update(kv, i, lay2)
        x = x + h
        h = _norm("mlp.norm", lp["mlp_norm"], x)
        if cfg.family == "moe":
            h, _ = moe_mod.moe(lp["moe"], h, n_experts=cfg.n_experts,
                               top_k=cfg.top_k, capacity_factor=cfg.moe_cf,
                               dense_kw=dense_kw)
        else:
            fn = (mlp_mod.gelu_mlp if cfg.mlp_type == "gelu"
                  else mlp_mod.swiglu)
            h = fn(lp["mlp"], h, dense_kw)
        return (x + h, kv), syn

    (x, kv), syns = jax.lax.scan(
        body, (x, kv), (jnp.arange(L, dtype=jnp.int32), params["layers"]))
    logits = _logits(params, cfg, x, dense_kw)
    if with_syndrome:
        return logits[:, 0], kv, syns.T        # (L, B) -> (B, L)
    return logits[:, 0], kv


def lm_verify_paged(
    params: dict[str, Any],
    cfg: ArchConfig,
    tokens: jax.Array,
    kv,
    block_tab: jax.Array,
    pos: jax.Array,
    *,
    page_size: int,
    dense_kw: dict[str, Any] | None = None,
    cache_dtype=jnp.bfloat16,
) -> tuple[jax.Array, Any]:
    """Speculative verify: V tokens per slot, one batched paged step.

    tokens: (B, V) int32 — each slot's current last token followed by
    ``V - 1`` drafted tokens, occupying positions ``pos[b] ..
    pos[b] + V - 1``;  kv/block_tab as in :func:`lm_decode_paged`.
    Returns ``(logits (B, V, vocab), kv)`` — row ``j`` is the target's
    distribution for the token *after* ``tokens[:, j]``, each computed
    over exactly the prefix a sequential decode would have seen (the
    per-row causal masking lives in the folded kernel dispatch,
    :func:`repro.numerics.attention.paged_verify`).  Layer structure,
    scan carry, and MLP path mirror :func:`lm_decode_paged` with the
    token axis widened from 1 to V — every weight matmul is the same
    resident residue matmul over V rows instead of one.
    """
    from repro.numerics import kv_pages as kvp

    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"paged verify supports dense/moe/vlm, not {cfg.family!r}")
    dense_kw = dense_kw or {}
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    V = tokens.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"]["table"].astype(compute_dtype)[tokens]  # (B,V,d)
        x = constrain(x, "dp", None, None)
    positions = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(
        V, dtype=jnp.int32)[None, :]
    akw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
               dense_kw=dense_kw, apply_rope=not cfg.is_encdec)
    L = cfg.n_layers

    def body(carry, inp):
        x, kv = carry
        i, lp = inp
        with jax.named_scope("kv.layer"):
            lay = kvp.layer_slice(kv, i)
        h, lay2 = attn_mod.paged_verify_attention(
            lp["attn"], _norm("attn.norm", lp["attn_norm"], x), lay,
            block_tab, positions, page_size=page_size,
            cache_dtype=cache_dtype, **akw)
        with jax.named_scope("kv.layer"):
            kv = kvp.layer_update(kv, i, lay2)
        x = x + h
        h = _norm("mlp.norm", lp["mlp_norm"], x)
        if cfg.family == "moe":
            h, _ = moe_mod.moe(lp["moe"], h, n_experts=cfg.n_experts,
                               top_k=cfg.top_k, capacity_factor=cfg.moe_cf,
                               dense_kw=dense_kw)
        else:
            fn = (mlp_mod.gelu_mlp if cfg.mlp_type == "gelu"
                  else mlp_mod.swiglu)
            h = fn(lp["mlp"], h, dense_kw)
        return (x + h, kv), None

    (x, kv), _ = jax.lax.scan(
        body, (x, kv), (jnp.arange(L, dtype=jnp.int32), params["layers"]))
    return _logits(params, cfg, x, dense_kw), kv
