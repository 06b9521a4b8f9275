"""Model assembly: one composable decoder/encoder-decoder transformer that
covers all 10 assigned architectures.

Layers are organised as ``prefix`` (unrolled leading layers, e.g. DeepSeek's
first-k-dense) followed by a **scan over periods**: the per-layer kind pattern
(attention vs SSM mixer, dense vs MoE mlp) repeats with period ``P`` (lcm of
the hybrid/MoE strides), so parameters are stacked ``(n_periods, ...)`` per
slot and the whole depth lowers to a single ``lax.scan`` — HLO size and
compile time stay bounded for 61-layer models.

Modes:
  * ``train``   — full causal pass, logits + losses, no cache.
  * ``prefill`` — causal pass that also fills the decode cache.
  * ``decode``  — one new token against the cache (S == 1).

Caches are pytrees mirroring the prefix/body structure, so they shard via the
same path-based rules as parameters (see :func:`param_spec`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.launch import sharding as shd
from repro.models import attention as attn
from repro.models import mlp as mlpm
from repro.models import ssm as ssmm
from repro.models.params import (KeyGen, dense_init, embed_init, ones,
                                 tree_slice, trunc_normal, zeros)
from repro.models.rope import positions_for

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# layer-kind layout
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerKind:
    mixer: str          # "gqa" | "mla" | "rwkv" | "mamba"
    mlp: str            # "dense" | "moe" | "cmix"
    cross: bool = False # decoder layer with cross attention (enc-dec)


def kind_for_layer(cfg: ModelConfig, i: int, *, cross: bool = False
                   ) -> LayerKind:
    if cfg.is_attention_layer(i):
        mixer = "mla" if cfg.attn_type == "mla" else "gqa"
    else:
        mixer = "rwkv" if (cfg.ssm and cfg.ssm.kind == "rwkv6") else "mamba"
    if cfg.ssm and cfg.ssm.kind == "rwkv6":
        ml = "cmix"
    elif cfg.is_moe_layer(i):
        ml = "moe"
    else:
        ml = "dense"
    return LayerKind(mixer, ml, cross)


def _try_layout(cfg: ModelConfig, prefix: int, P: int
                ) -> Optional[List[LayerKind]]:
    """Kinds for one period if layers [prefix:] repeat with period P."""
    body = cfg.num_layers - prefix
    if body <= 0 or body % P != 0:
        return None
    kinds = [kind_for_layer(cfg, prefix + j, cross=cfg.is_encoder_decoder)
             for j in range(P)]
    for j in range(body):
        if kind_for_layer(cfg, prefix + j,
                          cross=cfg.is_encoder_decoder) != kinds[j % P]:
            return None
    return kinds


def layer_layout(cfg: ModelConfig) -> Tuple[int, List[LayerKind], int]:
    """Returns (prefix_len, period_kinds, n_periods) for the decoder stack.

    Tries prefix=0 first (fully periodic stacks, e.g. Jamba's interleave
    where first_k_dense merely offsets the MoE stride), then pulls the
    leading dense layers (DeepSeek) out as an unrolled prefix, then falls
    back to one fat period.
    """
    P = 1
    if cfg.attn_period > 0:
        P = math.lcm(P, cfg.attn_period)
    if cfg.moe is not None and cfg.moe.every_k > 1:
        P = math.lcm(P, cfg.moe.every_k)
    for prefix in (0, cfg.moe.first_k_dense if cfg.moe else 0):
        kinds = _try_layout(cfg, prefix, P)
        if kinds is not None:
            return prefix, kinds, (cfg.num_layers - prefix) // P
    # degenerate: everything in one unrolled period
    kinds = _try_layout(cfg, 0, cfg.num_layers)
    assert kinds is not None
    return 0, kinds, 1


# ---------------------------------------------------------------------------
# single block (norm -> mixer -> +res -> [cross] -> norm -> mlp -> +res)
# ---------------------------------------------------------------------------


def _norm_init(cfg: ModelConfig, with_bias: bool) -> Params:
    p = {"scale": ones((cfg.d_model,), jnp.dtype(cfg.param_dtype))}
    if with_bias:
        p["bias"] = zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype))
    return p


def _norm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    import os
    if os.environ.get("REPRO_NORM_BF16"):
        # Hillclimb probe: norm statistics in the activation dtype, so the
        # upstream TP partial-sum all-reduce is not promoted to f32 by the
        # fused upcast (collective-term experiment; numerics differ).
        mu = jnp.mean(x, -1, keepdims=True) if "bias" in p else 0.0
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
        y = y * p["scale"]
        return y + p["bias"] if "bias" in p else y
    if "bias" in p:                            # LayerNorm (RWKV, seamless)
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)
    return ops.rmsnorm(x, p["scale"], eps)


def _uses_ln_bias(cfg: ModelConfig) -> bool:
    return (cfg.ssm is not None and cfg.ssm.kind == "rwkv6") or \
        cfg.family == "encdec"


def block_init(kg: KeyGen, cfg: ModelConfig, kind: LayerKind) -> Params:
    b = _uses_ln_bias(cfg)
    p: Params = {"norm1": _norm_init(cfg, b), "norm2": _norm_init(cfg, b)}
    if kind.mixer == "gqa":
        p["mixer"] = attn.gqa_init(kg, cfg)
    elif kind.mixer == "mla":
        p["mixer"] = attn.mla_init(kg, cfg)
    elif kind.mixer == "rwkv":
        p["mixer"] = ssmm.rwkv_tmix_init(kg, cfg)
    elif kind.mixer == "mamba":
        p["mixer"] = ssmm.mamba_init(kg, cfg)
    else:
        raise ValueError(kind.mixer)
    if kind.cross:
        p["cross_norm"] = _norm_init(cfg, b)
        p["cross"] = attn.cross_init(kg, cfg)
    if kind.mlp == "dense":
        d_ff = cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense) \
            else cfg.d_ff
        p["mlp"] = mlpm.mlp_init(kg, cfg, d_ff=d_ff)
    elif kind.mlp == "moe":
        p["mlp"] = mlpm.moe_init(kg, cfg)
    elif kind.mlp == "cmix":
        p["mlp"] = ssmm.rwkv_cmix_init(kg, cfg)
    else:
        raise ValueError(kind.mlp)
    return p


def block_cache(cfg: ModelConfig, kind: LayerKind, batch: int, max_len: int
                ) -> Params:
    """Decode-cache pytree for one block (zeros; filled by prefill)."""
    c: Params = {}
    if kind.mixer == "gqa":
        c["attn"] = attn.gqa_init_cache(cfg, batch, max_len)
    elif kind.mixer == "mla":
        c["attn"] = attn.mla_init_cache(cfg, batch, max_len)
    elif kind.mixer == "rwkv":
        H, K = cfg.num_heads, cfg.ssm.head_dim
        c["attn"] = {"last_x": jnp.zeros((batch, cfg.d_model), cfg.dtype),
                     "state": jnp.zeros((batch, H, K, K), jnp.float32)}
    elif kind.mixer == "mamba":
        s = cfg.ssm
        Din = s.expand * cfg.d_model
        c["attn"] = {"conv": jnp.zeros((batch, s.d_conv - 1, Din), cfg.dtype),
                     "h": jnp.zeros((batch, Din, s.d_state), jnp.float32)}
    if kind.mlp == "cmix":
        c["mlp"] = {"last_x": jnp.zeros((batch, cfg.d_model), cfg.dtype)}
    return c


def block_apply(
    p: Params,
    x: jax.Array,                   # (B, S, D)
    *,
    cfg: ModelConfig,
    kind: LayerKind,
    positions: jax.Array,
    mode: str,
    cache: Optional[Params],
    kv_len: Optional[jax.Array],
    memory: Optional[jax.Array] = None,       # (B, S_enc, D) enc-dec
    mrope_positions: Optional[jax.Array] = None,
    causal: bool = True,
) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (x_out, aux_loss, new_cache)."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    new_cache: Params = {}
    h = _norm(p["norm1"], x, eps)
    mix_cache = cache.get("attn") if cache else None
    if kind.mixer == "gqa":
        out, nc = attn.gqa_apply(p["mixer"], h, cfg=cfg, positions=positions,
                                 mode=mode, cache=mix_cache, kv_len=kv_len,
                                 mrope_positions=mrope_positions,
                                 causal=causal)
    elif kind.mixer == "mla":
        out, nc = attn.mla_apply(p["mixer"], h, cfg=cfg, positions=positions,
                                 mode=mode, cache=mix_cache, kv_len=kv_len,
                                 causal=causal)
    elif kind.mixer == "rwkv":
        out, nc = ssmm.rwkv_tmix_apply(p["mixer"], h, cfg=cfg, mode=mode,
                                       cache=mix_cache)
    elif kind.mixer == "mamba":
        out, nc = ssmm.mamba_apply(p["mixer"], h, cfg=cfg, mode=mode,
                                   cache=mix_cache)
    else:
        raise ValueError(kind.mixer)
    if nc is not None:
        new_cache["attn"] = nc
    x = x + out

    if kind.cross and memory is not None:
        hc = _norm(p["cross_norm"], x, eps)
        x = x + attn.cross_apply(p["cross"], hc, memory, cfg=cfg)

    h2 = _norm(p["norm2"], x, eps)
    if kind.mlp == "dense":
        x = x + mlpm.mlp_apply(p["mlp"], h2, cfg=cfg)
    elif kind.mlp == "moe":
        out, aux = mlpm.moe_apply(p["mlp"], h2, cfg=cfg)
        x = x + out
    elif kind.mlp == "cmix":
        out, nc = ssmm.rwkv_cmix_apply(p["mlp"], h2, cfg=cfg, mode=mode,
                                       cache=cache.get("mlp") if cache
                                       else None)
        if nc is not None:
            new_cache["mlp"] = nc
        x = x + out
    x = shd.logical(x, "batch", "seq", "embed")
    return x, aux, (new_cache if new_cache else None)


# ---------------------------------------------------------------------------
# full-model init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    kg = KeyGen(key)
    dt = jnp.dtype(cfg.param_dtype)
    Vp = cfg.padded_vocab()
    D = cfg.d_model
    p: Params = {"embed": embed_init(kg(), Vp, D, dtype=dt)}

    if cfg.is_encoder_decoder or (cfg.rope == "none" and cfg.ssm is None):
        # learned absolute positions for rope-free attention stacks
        p["pos_embed"] = trunc_normal(kg(), (cfg.max_seq_len, D), std=0.02,
                                      dtype=dt)
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        p["ln0"] = _norm_init(cfg, True)

    # encoder stack (uniform GQA blocks, non-causal, P=1)
    if cfg.is_encoder_decoder:
        enc_kind = LayerKind("gqa", "dense", False)
        enc_layers = [block_init(kg, cfg, enc_kind)
                      for _ in range(cfg.num_encoder_layers)]
        p["enc_body"] = [jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                      *enc_layers)]
        p["enc_norm"] = _norm_init(cfg, _uses_ln_bias(cfg))

    prefix, kinds, n_periods = layer_layout(cfg)
    if prefix:
        pk = [kind_for_layer(cfg, i, cross=cfg.is_encoder_decoder)
              for i in range(prefix)]
        p["prefix"] = [block_init(kg, cfg, k) for k in pk]
    body_slots = []
    for j, k in enumerate(kinds):
        periods = [block_init(kg, cfg, k) for _ in range(n_periods)]
        body_slots.append(jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                       *periods))
    p["body"] = body_slots
    p["final_norm"] = _norm_init(cfg, _uses_ln_bias(cfg))
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(kg(), D, Vp, std=1.0 / math.sqrt(D),
                                  dtype=dt)
    if cfg.mtp_depth > 0:
        mk = kind_for_layer(cfg, cfg.num_layers - 1)
        p["mtp"] = {
            "proj": dense_init(kg(), 2 * D, D, dtype=dt),
            "norm_h": _norm_init(cfg, False),
            "norm_e": _norm_init(cfg, False),
            "block": block_init(kg, cfg, mk),
            "final_norm": _norm_init(cfg, False),
        }
    return p


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    prefix, kinds, n_periods = layer_layout(cfg)
    c: Params = {}
    if prefix:
        pk = [kind_for_layer(cfg, i, cross=cfg.is_encoder_decoder)
              for i in range(prefix)]
        c["prefix"] = [block_cache(cfg, k, batch, max_len) for k in pk]
    slots = []
    for k in kinds:
        per = [block_cache(cfg, k, batch, max_len) for _ in range(n_periods)]
        slots.append(jax.tree.map(lambda *xs: jnp.stack(xs, 0), *per))
    c["body"] = slots
    return c


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _embed(p: Params, cfg: ModelConfig, tokens: jax.Array,
           positions: jax.Array) -> jax.Array:
    x = jnp.take(p["embed"], tokens, axis=0).astype(cfg.dtype)
    if "pos_embed" in p:
        x = x + jnp.take(p["pos_embed"], positions, axis=0).astype(cfg.dtype)
    if "ln0" in p:
        x = _norm(p["ln0"], x, cfg.norm_eps)
    return shd.logical(x, "batch", "seq", "embed")


def _run_stack(
    p: Params,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    positions: jax.Array,
    mode: str,
    cache: Optional[Params],
    kv_len: Optional[jax.Array],
    memory: Optional[jax.Array],
    mrope_positions: Optional[jax.Array],
    enc: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Prefix + scanned body. Returns (x, total_aux, new_cache)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Params = {}
    if enc:
        prefix, kinds, n_periods = 0, [LayerKind("gqa", "dense", False)], \
            cfg.num_encoder_layers
        body_key, prefix_key = "enc_body", None
        causal = False
    else:
        prefix, kinds, n_periods = layer_layout(cfg)
        body_key, prefix_key = "body", "prefix"
        causal = True
    P = len(kinds)

    if prefix:
        pc = []
        for i in range(prefix):
            k = kind_for_layer(cfg, i, cross=cfg.is_encoder_decoder)
            ci = cache["prefix"][i] if cache else None
            x, aux, nc = block_apply(
                p["prefix"][i], x, cfg=cfg, kind=k, positions=positions,
                mode=mode, cache=ci, kv_len=kv_len, memory=memory,
                mrope_positions=mrope_positions, causal=causal)
            aux_total = aux_total + aux
            pc.append(nc)
        if mode in ("prefill", "decode"):
            new_cache["prefix"] = pc

    with_cache = mode in ("prefill", "decode")

    def period_body(carry, xs):
        x, aux_acc = carry
        slot_params, slot_caches = xs
        ncs = []
        for j in range(P):
            cj = slot_caches[j] if slot_caches is not None else None
            x, aux, nc = block_apply(
                slot_params[j], x, cfg=cfg, kind=kinds[j],
                positions=positions, mode=mode, cache=cj, kv_len=kv_len,
                memory=memory, mrope_positions=mrope_positions,
                causal=causal)
            aux_acc = aux_acc + aux
            ncs.append(nc)
        ys = ncs if with_cache else None
        return (x, aux_acc), ys

    policy = _remat_policy(cfg)
    body_fn = period_body
    if policy is not None and mode == "train":
        body_fn = jax.checkpoint(period_body, policy=policy,
                                 prevent_cse=False)

    body_caches = cache[body_key] if (cache is not None and not enc) else None
    if not cfg.scan_layers:
        # unrolled python loop (dry-run cost probes; tiny smoke models)
        n_periods = jax.tree.leaves(p[body_key])[0].shape[0]
        ys_list = []
        carry = (x, aux_total)
        for t in range(n_periods):
            sp = [jax.tree.map(lambda a: a[t], slot) for slot in p[body_key]]
            sc = None
            if body_caches is not None:
                sc = [jax.tree.map(lambda a: a[t], slot)
                      for slot in body_caches]
            carry, ys_t = body_fn(carry, (sp, sc))
            ys_list.append(ys_t)
        x, aux_total = carry
        ys = jax.tree.map(lambda *xs_: jnp.stack(xs_, 0), *ys_list) \
            if (with_cache and ys_list) else None
    elif body_caches is None:
        # scan needs concrete xs; use params only and close over None caches
        def body_no_cache(carry, slot_params):
            return body_fn(carry, (slot_params, None))
        (x, aux_total), ys = jax.lax.scan(
            body_no_cache, (x, aux_total), p[body_key])
    else:
        (x, aux_total), ys = jax.lax.scan(body_fn, (x, aux_total),
                                          (p[body_key], body_caches))
    if with_cache and not enc:
        new_cache["body"] = ys
    return x, aux_total, (new_cache if with_cache else None)


@dataclasses.dataclass
class Output:
    logits: jax.Array                    # (B, S, Vp)
    aux_loss: jax.Array                  # scalar (MoE balance)
    cache: Optional[Params] = None
    hidden: Optional[jax.Array] = None   # pre-head hidden (for MTP)


def _head(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    head = p["lm_head"] if "lm_head" in p else p["embed"].T
    logits = x @ head
    return shd.logical(logits, "batch", "seq", "vocab")


def encode(p: Params, cfg: ModelConfig, enc_embeds: jax.Array) -> jax.Array:
    """Encoder forward from precomputed frame embeddings (stub frontend)."""
    B, S, _ = enc_embeds.shape
    positions = positions_for(B, S)
    x = enc_embeds.astype(cfg.dtype)
    if "pos_embed" in p:
        x = x + jnp.take(p["pos_embed"], positions, axis=0).astype(cfg.dtype)
    x = shd.logical(x, "batch", "seq", "embed")
    x, _, _ = _run_stack(p, x, cfg=cfg, positions=positions, mode="train",
                         cache=None, kv_len=None, memory=None,
                         mrope_positions=None, enc=True)
    return _norm(p["enc_norm"], x, cfg.norm_eps)


def forward(
    p: Params,
    batch: Dict[str, jax.Array],
    *,
    cfg: ModelConfig,
    mode: str = "train",
    cache: Optional[Params] = None,
    head: bool = True,
) -> Output:
    """batch keys: tokens (B,S); optional positions, kv_len, enc_embeds,
    patch_embeds + patch_positions (vlm), mrope_positions (3,B,S)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = positions_for(B, S)

    memory = None
    if cfg.is_encoder_decoder:
        memory = batch.get("memory")
        if memory is None:
            memory = encode(p, cfg, batch["enc_embeds"])

    x = _embed(p, cfg, tokens, positions)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        # scatter precomputed patch embeddings into the token stream
        pe = batch["patch_embeds"].astype(x.dtype)      # (B, n_patch, D)
        pp = batch["patch_positions"]                   # (B, n_patch) int32
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
        x = x.at[bidx, pp].set(pe)

    mrope = batch.get("mrope_positions")
    x, aux, new_cache = _run_stack(
        p, x, cfg=cfg, positions=positions, mode=mode, cache=cache,
        kv_len=batch.get("kv_len"), memory=memory, mrope_positions=mrope)
    hidden = x
    x = _norm(p["final_norm"], x, cfg.norm_eps)
    logits = _head(p, cfg, x) if head else x    # !head: normed hidden
    return Output(logits=logits, aux_loss=aux, cache=new_cache, hidden=hidden)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def _xent(logits: jax.Array, labels: jax.Array, valid: jax.Array,
          vocab_size: int) -> jax.Array:
    """Masked mean cross-entropy. logits (B,S,Vp) any dtype, labels (B,S)."""
    lg = logits.astype(jnp.float32)
    Vp = lg.shape[-1]
    if Vp > vocab_size:
        pad_mask = jnp.arange(Vp) < vocab_size
        lg = jnp.where(pad_mask, lg, -1e30)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = (lse - tgt) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)


def _xent_chunked(p: Params, cfg: ModelConfig, hidden_normed: jax.Array,
                  labels: jax.Array, valid: jax.Array) -> jax.Array:
    """Memory-lean loss: project + cross-entropy one sequence chunk at a
    time, so peak logits memory is (B, chunk, V) instead of (B, S, V).
    Beyond-paper memory-term optimization."""
    B, S, D = hidden_normed.shape
    C = min(cfg.loss_chunk, S)
    n = S // C
    rem = S - n * C
    head = p["lm_head"] if "lm_head" in p else p["embed"].T

    def chunk_loss(x_c, lab_c, val_c):
        logits = shd.logical(x_c @ head, "batch", "seq", "vocab")
        lg = logits.astype(jnp.float32)
        Vp = lg.shape[-1]
        if Vp > cfg.vocab_size:
            lg = jnp.where(jnp.arange(Vp) < cfg.vocab_size, lg, -1e30)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, lab_c[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * val_c)

    xm = hidden_normed[:, :n * C].reshape(B, n, C, D)
    lm = labels[:, :n * C].reshape(B, n, C)
    vm = valid[:, :n * C].reshape(B, n, C)
    if cfg.scan_layers:
        def body(acc, xs_):
            x_c, lab_c, val_c = xs_
            return acc + chunk_loss(x_c, lab_c, val_c), None
        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (jnp.moveaxis(xm, 1, 0), jnp.moveaxis(lm, 1, 0),
             jnp.moveaxis(vm, 1, 0)))
    else:
        total = jnp.zeros((), jnp.float32)
        for i in range(n):
            total = total + chunk_loss(xm[:, i], lm[:, i], vm[:, i])
    if rem:
        total = total + chunk_loss(hidden_normed[:, n * C:],
                                   labels[:, n * C:], valid[:, n * C:])
    return total / jnp.maximum(jnp.sum(valid), 1.0)


def _mtp_loss(p: Params, cfg: ModelConfig, hidden: jax.Array,
              tokens: jax.Array, labels2: jax.Array, valid2: jax.Array,
              positions: jax.Array) -> jax.Array:
    """DeepSeek-V3 MTP (depth 1): predict t+2 from [norm(h_t); norm(E(t+1))]."""
    m = p["mtp"]
    nxt = jnp.roll(tokens, -1, axis=1)                 # token t+1
    e = jnp.take(p["embed"], nxt, axis=0).astype(cfg.dtype)
    h = jnp.concatenate([_norm(m["norm_h"], hidden, cfg.norm_eps),
                         _norm(m["norm_e"], e, cfg.norm_eps)], axis=-1)
    h = h @ m["proj"]
    kind = kind_for_layer(cfg, cfg.num_layers - 1)
    h, _, _ = block_apply(m["block"], h, cfg=cfg, kind=kind,
                          positions=positions, mode="train", cache=None,
                          kv_len=None)
    h = _norm(m["final_norm"], h, cfg.norm_eps)
    if cfg.loss_chunk > 0:
        return _xent_chunked(p, cfg, h, labels2, valid2)
    logits = _head(p, cfg, h)
    return _xent(logits, labels2, valid2, cfg.vocab_size)


def loss_fn(
    p: Params,
    batch: Dict[str, jax.Array],
    *,
    cfg: ModelConfig,
    mtp_weight: float = 0.3,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token LM loss (+ MoE aux + MTP). batch["tokens"]: (B, S+1) —
    inputs are [:, :-1], labels are [:, 1:]."""
    toks = batch["tokens"]
    inputs, labels = toks[:, :-1], toks[:, 1:]
    fb = dict(batch)
    fb["tokens"] = inputs
    chunked = cfg.loss_chunk > 0
    out = forward(p, fb, cfg=cfg, mode="train", head=not chunked)
    valid = jnp.ones(labels.shape, jnp.float32)
    if "loss_mask" in batch:
        valid = batch["loss_mask"][:, 1:].astype(jnp.float32)
    if chunked:
        loss = _xent_chunked(p, cfg, out.logits, labels, valid)
    else:
        loss = _xent(out.logits, labels, valid, cfg.vocab_size)
    metrics = {"lm_loss": loss}
    if cfg.moe is not None:
        metrics["aux_loss"] = out.aux_loss
        loss = loss + cfg.moe.aux_loss_coef * out.aux_loss
    if cfg.mtp_depth > 0:
        labels2 = jnp.roll(labels, -1, axis=1)         # token t+2
        valid2 = valid.at[:, -1].set(0.0)
        pos = batch.get("positions")
        if pos is None:
            pos = positions_for(*inputs.shape)
        lm = _mtp_loss(p, cfg, out.hidden, inputs, labels2, valid2, pos)
        metrics["mtp_loss"] = lm
        loss = loss + mtp_weight * lm
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------


def prefill(
    p: Params,
    batch: Dict[str, jax.Array],
    *,
    cfg: ModelConfig,
    max_len: int,
) -> Tuple[jax.Array, Params]:
    """Run the prompt, return (last-token logits (B,Vp), filled cache)."""
    B, S = batch["tokens"].shape
    cache = init_cache(cfg, B, max_len)
    out = forward(p, batch, cfg=cfg, mode="prefill", cache=cache)
    return out.logits[:, -1], out.cache


def decode_step(
    p: Params,
    token: jax.Array,               # (B,) int32 — the newest token
    pos: jax.Array,                 # scalar/(B,) its absolute position
    cache: Params,
    *,
    cfg: ModelConfig,
    kv_len: Optional[jax.Array] = None,
    memory: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """One decode step: logits for the next token + updated cache."""
    B = token.shape[0]
    batch = {"tokens": token[:, None],
             "positions": positions_for(B, 1, pos)}
    if kv_len is not None:
        batch["kv_len"] = kv_len
    if memory is not None:
        batch["memory"] = memory
    out = forward(p, batch, cfg=cfg, mode="decode", cache=cache)
    return out.logits[:, 0], out.cache


# ---------------------------------------------------------------------------
# parameter / cache sharding specs (path-based logical rules)
# ---------------------------------------------------------------------------

# leaf name -> logical spec for the *trailing* dims (leading stack dims pad
# with None). Names not listed replicate.
_SPEC_BY_NAME: Dict[str, Tuple] = {
    # embeddings / head
    "embed": ("vocab", "embed"),
    "lm_head": ("embed", "vocab"),
    "pos_embed": (None, "embed"),
    # attention
    "wq": ("embed", "heads"),
    "wk": ("embed", "heads"),
    "wv": ("embed", "heads"),
    "wo": ("heads", "embed"),
    "bq": ("heads",),
    "bk": ("heads",),
    "bv": ("heads",),
    # mla
    "wq_a": ("embed", None),
    "wq_b": (None, "heads"),
    "wkv_a": ("embed", None),
    "wkv_b": (None, "heads"),
    # mlp
    "w_gate": ("embed", "ff"),
    "w_up": ("embed", "ff"),
    "w_down": ("ff", "embed"),
    "b_up": ("ff",),
    # rwkv
    "wr": ("embed", "heads"),
    "wg": ("embed", "heads"),
    "lora_a": ("embed", None),
    "decay_a": ("embed", None),
    # mamba
    "in_proj": ("embed", "ff"),
    "x_proj": ("ff", None),
    "dt_proj": (None, "ff"),
    "out_proj": ("ff", "embed"),
    "conv_w": (None, "ff"),
    "conv_b": ("ff",),
    "A_log": ("ff", None),
    "D": ("ff",),
    # mtp
    "proj": (None, "embed"),
}

# MoE expert stacks are 3-D (E, d_in, d_out): ff dim sharded over model.
_MOE_3D = {"w_gate": (None, None, "ff"), "w_up": (None, None, "ff"),
           "w_down": (None, "ff", None)}


def _leaf_logical_spec(path: str, ndim: int) -> Tuple:
    name = path.split("/")[-1]
    spec: Optional[Tuple] = None
    if name in ("w_gate", "w_up", "w_down"):
        # distinguish dense MLP (2-D trailing) from expert stacks (3-D)
        spec = _MOE_3D[name] if (ndim >= 3 and _is_expert_stack(path)) \
            else _SPEC_BY_NAME[name]
    elif name in _SPEC_BY_NAME:
        spec = _SPEC_BY_NAME[name]
    if spec is None:
        return (None,) * ndim
    pad = ndim - len(spec)
    if pad < 0:                      # leaf smaller than spec (shouldn't happen)
        return (None,) * ndim
    return (None,) * pad + tuple(spec)


def _is_expert_stack(path: str) -> bool:
    """Expert stacks live under an mlp dict that also has a router leaf —
    path ends .../mlp/w_gate and the mlp is a MoE. We detect via path marker
    set at spec-build time (see param_spec which passes sibling info)."""
    return getattr(_is_expert_stack, "_moe_paths", frozenset()) and \
        any(path.startswith(m) for m in _is_expert_stack._moe_paths)


def _iter_paths(tree: Any, prefix: str = ""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _iter_paths(v, f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_paths(v, f"{prefix}/{i}")
    else:
        yield prefix, tree


def _map_with_paths(tree: Any, fn, prefix: str = ""):
    if isinstance(tree, dict):
        return {k: _map_with_paths(v, fn, f"{prefix}/{k}")
                for k, v in tree.items()}
    if isinstance(tree, list):
        return [_map_with_paths(v, fn, f"{prefix}/{i}")
                for i, v in enumerate(tree)]
    if isinstance(tree, tuple):
        return tuple(_map_with_paths(v, fn, f"{prefix}/{i}")
                     for i, v in enumerate(tree))
    return fn(prefix, tree)


def param_spec(params: Params):
    """PartitionSpec pytree for ``params`` under the active axis rules."""
    # mark MoE mlp dicts (they contain a "router" leaf)
    moe_paths = set()
    for path, _ in _iter_paths(params):
        if path.endswith("/router"):
            moe_paths.add(path[:-len("router")])
    _is_expert_stack._moe_paths = frozenset(moe_paths)

    def fn(path, leaf):
        spec = _leaf_logical_spec(path, leaf.ndim)
        return shd.resolve_spec(leaf.shape, spec)
    return _map_with_paths(params, fn)


_CACHE_SPEC = {
    # gqa cache (B, C, KV, Dh); mla (B, C, lora) / (B, C, dr)
    "k": ("batch", "seq", "kv_heads", None),
    "v": ("batch", "seq", "kv_heads", None),
    "c": ("batch", "seq", None),
    "kr": ("batch", "seq", None),
    # ssm states
    "last_x": ("batch", "embed"),
    "state": ("batch", "heads", None, None),
    "conv": ("batch", None, "ff"),
    "h": ("batch", "ff", None),
}


def cache_spec(cache: Params):
    def fn(path, leaf):
        name = path.split("/")[-1]
        spec = tuple(_CACHE_SPEC.get(name, ()))
        pad = leaf.ndim - len(spec)
        if pad < 0:
            spec = (None,) * leaf.ndim
        else:
            spec = (None,) * pad + spec
        return shd.resolve_spec(leaf.shape, spec)
    return _map_with_paths(cache, fn)
