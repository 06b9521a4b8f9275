"""Public model API: a ``Model`` facade over the composable transformer
assembly plus per-(arch, shape) abstract input specs.

``input_specs`` returns ``jax.ShapeDtypeStruct`` stand-ins for every model
input of a given cell — weak-type-correct, shardable, no device allocation —
which is what the multi-pod dry-run lowers against. Modality frontends
(vision/audio) are STUBS per assignment: specs provide precomputed
patch/frame embeddings; the backbone is real.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tfm

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Model:
    """Thin, stateless facade: all methods are pure functions of params."""
    cfg: ModelConfig

    # -- construction ------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        return tfm.init_params(self.cfg, key)

    def init_cache(self, batch: int, max_len: int) -> Params:
        return tfm.init_cache(self.cfg, batch, max_len)

    # -- execution ---------------------------------------------------------
    def forward(self, params: Params, batch: Dict[str, jax.Array],
                mode: str = "train", cache: Optional[Params] = None
                ) -> tfm.Output:
        return tfm.forward(params, batch, cfg=self.cfg, mode=mode,
                           cache=cache)

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        return tfm.loss_fn(params, batch, cfg=self.cfg)

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                max_len: int) -> Tuple[jax.Array, Params]:
        return tfm.prefill(params, batch, cfg=self.cfg, max_len=max_len)

    def decode_step(self, params: Params, token, pos, cache,
                    kv_len=None, memory=None) -> Tuple[jax.Array, Params]:
        return tfm.decode_step(params, token, pos, cache, cfg=self.cfg,
                               kv_len=kv_len, memory=memory)

    # -- sharding ----------------------------------------------------------
    def param_spec(self, params: Params):
        return tfm.param_spec(params)

    def cache_spec(self, cache: Params):
        return tfm.cache_spec(cache)

    def abstract_params(self, key: Optional[jax.Array] = None) -> Params:
        k = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda: tfm.init_params(self.cfg, k))

    def abstract_cache(self, batch: int, max_len: int) -> Params:
        return jax.eval_shape(
            lambda: tfm.init_cache(self.cfg, batch, max_len))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# ---------------------------------------------------------------------------
# abstract input specs per (arch family, shape cell)
# ---------------------------------------------------------------------------

I32 = jnp.int32
F32 = jnp.float32


def _sds(shape, dtype=I32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def train_input_specs(cfg: ModelConfig, shape: ShapeConfig
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Inputs for ``loss_fn``: tokens (B, S+1) plus modality extras."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.is_encoder_decoder:
        # budget: S_enc = S_dec = S/2
        Se = Sd = S // 2
        return {
            "tokens": _sds((B, Sd + 1)),
            "enc_embeds": _sds((B, Se, cfg.d_model), F32),
        }
    specs = {"tokens": _sds((B, S + 1))}
    if cfg.frontend == "vision":
        n_patch = max(1, S // 4)                 # stub: 25% image patches
        specs["patch_embeds"] = _sds((B, n_patch, cfg.d_model), F32)
        specs["patch_positions"] = _sds((B, n_patch))
    if cfg.rope == "mrope":
        specs["mrope_positions"] = _sds((3, B, S))
    return specs


def prefill_input_specs(cfg: ModelConfig, shape: ShapeConfig
                        ) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq_len
    if cfg.is_encoder_decoder:
        Se = Sd = S // 2
        return {
            "tokens": _sds((B, Sd)),
            "enc_embeds": _sds((B, Se, cfg.d_model), F32),
        }
    specs = {"tokens": _sds((B, S))}
    if cfg.frontend == "vision":
        n_patch = max(1, S // 4)
        specs["patch_embeds"] = _sds((B, n_patch, cfg.d_model), F32)
        specs["patch_positions"] = _sds((B, n_patch))
    if cfg.rope == "mrope":
        specs["mrope_positions"] = _sds((3, B, S))
    return specs


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig
                       ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Inputs for one ``decode_step`` with a KV cache of ``seq_len``."""
    B = shape.global_batch
    specs = {
        "token": _sds((B,)),
        "pos": _sds(()),
        "kv_len": _sds((B,)),
    }
    if cfg.is_encoder_decoder:
        Se = shape.seq_len // 2
        specs["memory"] = _sds((B, Se, cfg.d_model), F32)
    return specs


def input_specs(cfg: ModelConfig, shape: ShapeConfig
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    if shape.kind == "train":
        return train_input_specs(cfg, shape)
    if shape.kind == "prefill":
        return prefill_input_specs(cfg, shape)
    return decode_input_specs(cfg, shape)


def make_concrete(specs: Dict[str, jax.ShapeDtypeStruct], cfg: ModelConfig,
                  key: jax.Array) -> Dict[str, jax.Array]:
    """Random concrete inputs matching ``specs`` (for smoke tests)."""
    out = {}
    for name, s in specs.items():
        key, sub = jax.random.split(key)
        if name in ("tokens", "token"):
            out[name] = jax.random.randint(sub, s.shape, 0, cfg.vocab_size,
                                           dtype=s.dtype)
        elif name == "patch_positions":
            # distinct in-range positions per row
            n = s.shape[-1]
            out[name] = jnp.broadcast_to(
                jnp.arange(n, dtype=s.dtype), s.shape)
        elif name == "mrope_positions":
            S = s.shape[-1]
            base = jnp.arange(S, dtype=s.dtype)
            out[name] = jnp.broadcast_to(base, s.shape)
        elif name == "pos":
            out[name] = jnp.asarray(0, s.dtype)
        elif name == "kv_len":
            out[name] = jnp.ones(s.shape, s.dtype)
        else:
            out[name] = jax.random.normal(sub, s.shape, s.dtype) * 0.02
    return out
