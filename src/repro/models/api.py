"""Model facade: one uniform interface over all architecture families.

    model = Model(cfg)
    params = model.init(key)                      # dense training params
    params = model.init_synthetic(key)            # EVA serving params
    logits, _ = model.forward(params, batch, rc)  # train-mode forward
    loss = model.loss(params, batch, rc)
    caches = model.init_cache(batch_size, max_len)
    logits, caches = model.prefill(params, batch, rc)
    logits, caches = model.decode(params, tokens, positions, caches, rc)

    model.input_specs(shape)        # ShapeDtypeStruct inputs for dry-runs
    model.param_specs(quantized)    # ShapeDtypeStruct params (no alloc)
    model.cache_specs(batch, seq)   # ShapeDtypeStruct caches
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantize import quantize_params
from repro.models import common as cm
from repro.models import rglru, transformer, vision, whisper, xlstm
from repro.models.common import ModelConfig, RunConfig

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "xlstm": xlstm,
    "rglru": rglru,
    "whisper": whisper,
    "vision": vision,
}

# assigned input shapes: name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    @property
    def module(self):
        return _FAMILY[self.cfg.family]

    # ------------------------------------------------------------------ init
    def init(self, key) -> Any:
        return self.module.init_params(key, self.cfg)

    def quantize(self, params, *, method: str = "fit", key=None,
                 quantize_lm_head: bool = False, mesh=None,
                 report=None) -> Any:
        """`mesh` enables shard-aware grouping (families whose member
        boundaries are not shard-aligned under the target mesh stay
        ungrouped); `report` (a list) captures every grouping decision."""
        return quantize_params(params, self.cfg, method=method, key=key,
                               quantize_lm_head=quantize_lm_head,
                               mesh=mesh, report=report)

    def init_synthetic(self, key, *, quantize_lm_head: bool = False) -> Any:
        """EVA serving params with synthetic VQ weights, built on the
        device in one program: ``quantize(init(key), method="synthetic")``
        under jit. Synthetic VQ leaves depend only on their shapes and
        ``key``, so XLA drops the dense initializers they replace and no
        dense weight matrix is materialized — a full-width model fits
        wherever its quantized form does. The leaves that stay dense
        (embedding, lm_head, norms) come from the model's own initializer,
        cast to their serving dtype inside the same program."""
        def build(k):
            return self.quantize(self.init(k), method="synthetic", key=k,
                                 quantize_lm_head=quantize_lm_head)

        return jax.jit(build)(key)

    # --------------------------------------------------------------- forward
    def _extra_kwargs(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        kw = {}
        if self.cfg.family == "whisper" and "frames" in batch:
            kw["frames"] = batch["frames"]
        if self.cfg.family == "vision" and "image_embeds" in batch:
            kw["image_embeds"] = batch["image_embeds"]
        return kw

    def forward(self, params, batch: Dict[str, Any], rc: RunConfig,
                caches=None, stats: Optional[Dict[str, Any]] = None
                ) -> Tuple[jax.Array, Any]:
        """``stats`` (a dict, transformer families) receives the step's
        traced counters, e.g. ``moe_expert_visits``."""
        kw = self._extra_kwargs(batch)
        if stats is not None:
            kw["stats"] = stats
        return self.module.forward(
            params, batch["tokens"], rc, self.cfg,
            positions=batch.get("positions"), caches=caches, **kw,
        )

    def loss(self, params, batch: Dict[str, Any], rc: RunConfig) -> jax.Array:
        logits, _ = self.forward(params, batch, rc)
        logits = self._mask_pad_vocab(logits)
        return cm.cross_entropy_loss(logits, batch["labels"],
                                     batch.get("loss_mask"))

    def _mask_pad_vocab(self, logits):
        pad = self.cfg.padded_vocab - self.cfg.vocab_size
        if pad:
            neg = jnp.full((*logits.shape[:-1], pad), -1e30, logits.dtype)
            logits = jnp.concatenate(
                [logits[..., : self.cfg.vocab_size], neg], axis=-1
            )
        return logits

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_len: int, dtype=None,
                   kv_int8: bool = False, kv_int4: bool = False,
                   kvq=None) -> Any:
        """Allocate decode caches. ``kv_int8``/``kv_int4``/``kvq`` (a
        core.vq.KVQuantConfig — vector-quantized uint8-index KV) select
        compressed layouts on the attention families; other families
        ignore them (recurrent state is not a KV cache)."""
        if kvq is not None and self.cfg.family in ("dense", "moe"):
            return self.module.init_cache(self.cfg, batch, max_len, dtype,
                                          kvq=kvq)
        if (kv_int8 or kv_int4) and self.cfg.family in ("dense", "moe"):
            return self.module.init_cache(self.cfg, batch, max_len, dtype,
                                          kv_int8=kv_int8, kv_int4=kv_int4)
        return self.module.init_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, params, batch: Dict[str, Any], rc: RunConfig):
        rc = rc.replace(mode="prefill")
        logits, caches = self.forward(params, batch, rc)
        return logits, caches

    def decode(self, params, tokens, positions, caches, rc: RunConfig,
               stats: Optional[Dict[str, Any]] = None):
        """tokens (B,1), positions (B,1)."""
        rc = rc.replace(mode="decode")
        batch = {"tokens": tokens, "positions": positions}
        return self.forward(params, batch, rc, caches=caches, stats=stats)

    # ------------------------------------------------------------- dry-run
    def input_specs(self, shape: str, *, global_batch: Optional[int] = None,
                    kv_int8: bool = False, kv_int4: bool = False
                    ) -> Tuple[str, Dict[str, Any]]:
        """Returns (step_kind, specs). decode shapes include cache specs."""
        seq, gb, kind = SHAPES[shape]
        gb = global_batch or gb
        i32 = jnp.int32
        specs: Dict[str, Any] = {}
        if kind == "train":
            specs["tokens"] = jax.ShapeDtypeStruct((gb, seq), i32)
            specs["labels"] = jax.ShapeDtypeStruct((gb, seq), i32)
        elif kind == "prefill":
            specs["tokens"] = jax.ShapeDtypeStruct((gb, seq), i32)
        else:  # decode: one new token against a cache of length seq
            specs["tokens"] = jax.ShapeDtypeStruct((gb, 1), i32)
            specs["positions"] = jax.ShapeDtypeStruct((gb, 1), i32)
            specs["caches"] = self.cache_specs(gb, seq, kv_int8=kv_int8,
                                               kv_int4=kv_int4)
        if self.cfg.family == "whisper" and kind != "decode":
            specs["frames"] = jax.ShapeDtypeStruct(
                (gb, whisper.S_SRC, self.cfg.d_model), self.cfg.act_dtype
            )
        if self.cfg.family == "vision" and kind != "decode":
            specs["image_embeds"] = jax.ShapeDtypeStruct(
                (gb, vision.N_IMG_TOKENS, self.cfg.d_model), self.cfg.act_dtype
            )
        return kind, specs

    def param_specs(self, *, quantized: bool = False,
                    quantize_lm_head: bool = False) -> Any:
        dense = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        if not quantized:
            return dense
        return quantize_params(dense, self.cfg, method="specs",
                               quantize_lm_head=quantize_lm_head)

    def cache_specs(self, batch: int, max_len: int, kv_int8: bool = False,
                    kv_int4: bool = False, kvq=None) -> Any:
        """ShapeDtypeStruct cache tree for the given compression knobs
        (used by serve/paging.py byte accounting and launch dry-runs)."""
        return jax.eval_shape(
            functools.partial(self.init_cache, batch, max_len,
                              kv_int8=kv_int8, kv_int4=kv_int4, kvq=kvq)
        )

    def supports_shape(self, shape: str) -> bool:
        """long_500k only for sub-quadratic archs (see DESIGN.md §4)."""
        if shape != "long_500k":
            return True
        if self.cfg.family in ("xlstm", "rglru"):
            return True
        # SWA bounds the cache -> sub-quadratic decode state
        return self.cfg.sliding_window > 0


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def param_count(params) -> int:
    return sum(
        x.size for x in jax.tree_util.tree_leaves(params)
        if hasattr(x, "size")
    )
