"""Model-level VQ quantization pass: converts a dense checkpoint into the
EVA serving representation by replacing every eligible FC weight
(attention projections, MLP/expert matrices) with a VQWeight
(indices + additive codebooks + per-channel scale).

Embeddings, lm_head, norms, routers, gates, convs and recurrence
parameters stay high-precision — the same split as the paper (attention
computation and non-FC parameters remain FP16).

Same-input projection families are GROUPED by default: wq/wk/wv of an
attention block (GQA attention AND xlstm's mLSTM block, whose q/k/v all
consume the up-projected h) become one "wqkv" leaf, MLA's wq/wkv_a pair
(both consume the block input x) becomes "wq_kva", and gate/up of an MLP
become "gu" — each a single wide VQWeight with recorded split points
(see core/vq.py's grouped-codebook layout). The model layers then issue
ONE EVA matmul per family and slice the output, amortizing the VQ-GEMM /
output-codebook computation g-fold. Cross-attention blocks (whisper
"cross_attn", vision "xattn") are excluded — their q projection consumes
a different input than k/v.

Shard-aware grouping: pass the target ``mesh`` (or a model-axis shard
count) and a family whose member boundaries do NOT land on shard
boundaries of the wide N axis is left UNGROUPED — the members keep clean
column sharding instead of the grouped leaf silently falling back to
V-sharding with a per-layer psum (the splits_shard_aligned rule shared
with runtime/sharding.py). Every grouping decision can be captured in a
``report`` list for inspection.

Three methods:
  fit        — k-means additive VQ on real weights (small/smoke models)
  synthetic  — random valid indices/codebooks (benchmarks, huge dry-runs)
  specs      — ShapeDtypeStruct stand-ins (lowering only, no allocation)
"""
from __future__ import annotations

import zlib
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vq import (KVQuantConfig, VQWeight, fit_kv_codebooks, fit_vq,
                           kv_grid_codebooks, splits_shard_aligned,
                           synthetic_vq, vq_specs)

if TYPE_CHECKING:  # only for annotations — avoids a core<->models cycle
    from repro.models.common import ModelConfig

# param-tree path segments under which FC weights live
_BLOCK_SEGMENTS = (
    "layers", "pre_layers", "groups", "trail", "encoder", "decoder", "experts",
)
_MIN_DIM = 64  # don't quantize tiny matrices (per-head gates etc.)

# same-input projection families: (member keys, grouped key, required
# sibling that disambiguates the layout consumer). "wo" distinguishes
# attention_fwd's dict from xlstm's mlstm block (which also has wq/wk/wv
# but consumes them itself — its family is anchored by "w_if" instead);
# "down" anchors mlp_fwd/_expert_ffn; "wkv_b" is unique to the MLA dict,
# whose wq and wkv_a both consume the block input x.
_GROUP_FAMILIES = (
    (("wq", "wk", "wv"), "wqkv", "wo"),       # attention_fwd qkv
    (("wq", "wk", "wv"), "wqkv", "w_if"),     # xlstm mlstm qkv (input: h)
    (("wq", "wkv_a"), "wq_kva", "wkv_b"),     # MLA q + kv_a (input: x)
    (("gate", "up"), "gu", "down"),
)
# dict names whose members do NOT share an input (cross-attention)
_NO_GROUP_KEYS = ("cross_attn", "xattn")


def _eligible(path: Tuple[str, ...], w) -> bool:
    if not any(seg in path for seg in _BLOCK_SEGMENTS):
        return False
    if w.ndim < 2:
        return False
    K, N = w.shape[-2], w.shape[-1]
    return K >= _MIN_DIM and N >= _MIN_DIM


def _quantize_leaf(w, cfg: ModelConfig, method: str, key,
                   splits: Tuple[int, ...] = ()) -> VQWeight:
    """w: (..., K, N) possibly with stacked leading dims. `splits` marks w
    as the column-concatenation of a grouped projection family."""
    lead = w.shape[:-2]
    K, N = w.shape[-2], w.shape[-1]
    d, n, C = cfg.vq_d, cfg.vq_n, cfg.vq_C
    if K % d != 0:
        raise ValueError(f"K={K} not divisible by vq_d={d}")
    V = K // d
    k = 2 ** n
    idx_dtype = jnp.uint8 if n <= 8 else jnp.int32

    if method == "specs":
        return VQWeight(
            idx=jax.ShapeDtypeStruct((*lead, C, V, N), idx_dtype),
            codebooks=jax.ShapeDtypeStruct((*lead, C, d, k), jnp.float32),
            scale=jax.ShapeDtypeStruct((*lead, N), jnp.float32),
            K=K, N=N, d=d, n=n, splits=splits,
        )
    if method == "synthetic":
        # crc32, not hash(): str hashes are salted per process, and the
        # same seed must give the same weights in every run
        kk = jax.random.fold_in(key, zlib.crc32(str(w.shape).encode())
                                % (2 ** 31))
        base = synthetic_vq(kk, K, N, d=d, n=n, C=C, splits=splits)
        # indices must differ per stacked layer — tile with per-layer perm-ish noise
        if lead:
            nlead = int(np.prod(lead))
            keys = jax.random.split(kk, nlead)
            idx = jax.vmap(
                lambda k_: jax.random.randint(k_, (C, V, N), 0, k).astype(idx_dtype)
            )(keys).reshape(*lead, C, V, N)
            cbs = jax.vmap(
                lambda k_: (jax.random.normal(k_, (C, d, k)) / np.sqrt(K * C))
            )(keys).reshape(*lead, C, d, k)
            return VQWeight(idx=idx, codebooks=cbs,
                            scale=jnp.ones((*lead, N), jnp.float32),
                            K=K, N=N, d=d, n=n, splits=splits)
        return base
    if method == "fit":
        flat = w.reshape(-1, K, N)
        keys = jax.random.split(key, flat.shape[0])

        def fit_one(args):
            kk, wi = args
            return fit_vq(kk, wi, d=d, n=n, C=C, kmeans_iters=10, refine_rounds=0)

        vqs = jax.lax.map(fit_one, (keys, flat))
        def reshape_leaf(a):
            return a.reshape(*lead, *a.shape[1:]) if lead else a[0]
        return VQWeight(
            idx=reshape_leaf(vqs.idx),
            codebooks=reshape_leaf(vqs.codebooks),
            scale=reshape_leaf(vqs.scale),
            K=K, N=N, d=d, n=n, splits=splits,
        )
    raise ValueError(f"unknown method {method}")


_BF16_MIN_SIZE = 65536  # large non-VQ serving leaves (emb/lm_head) -> bf16


def _to_serving_dtype(leaf):
    """Cast large fp32 dense leaves to bf16 for serving (embeddings and
    lm_head stay unquantized per the paper but need not stay fp32)."""
    if not hasattr(leaf, "dtype") or leaf.dtype != jnp.float32:
        return leaf
    if int(np.prod(leaf.shape)) < _BF16_MIN_SIZE:
        return leaf
    if isinstance(leaf, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16)
    return leaf.astype(jnp.bfloat16)


def _concat_cols(leaves):
    """Column-concatenate member leaves; ShapeDtypeStructs are synthesized
    (specs mode never allocates)."""
    if isinstance(leaves[0], jax.ShapeDtypeStruct):
        shp = leaves[0].shape
        N = sum(l.shape[-1] for l in leaves)
        return jax.ShapeDtypeStruct((*shp[:-1], N), leaves[0].dtype)
    return jnp.concatenate(leaves, axis=-1)


def _model_shards(mesh) -> int:
    """Number of ways the 'model' mesh axis splits N. Accepts a Mesh /
    AbstractMesh (anything with .shape and .axis_names) or a bare int
    shard count; None -> 1 (shard-agnostic grouping)."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        return max(mesh, 1)
    if "model" not in getattr(mesh, "axis_names", ()):
        return 1
    return int(mesh.shape["model"])


def quantize_params(params: Any, cfg: ModelConfig, *, method: str = "fit",
                    key: Optional[jax.Array] = None,
                    serving_bf16: bool = True,
                    quantize_lm_head: bool = False,
                    group_projections: bool = True,
                    mesh: Union[None, int, Any] = None,
                    report: Optional[List[Dict[str, Any]]] = None) -> Any:
    """Walk the param tree and replace eligible {"w": ...} linears with
    {"vq": VQWeight} (preserving biases). Remaining large dense leaves
    (embeddings, lm_head) are cast to bf16 when `serving_bf16`.
    `quantize_lm_head` additionally VQ-compresses the output projection —
    beyond the paper (which keeps it FP16); worth ~0.3 GB/device of decode
    traffic on qwen2-72b (EXPERIMENTS.md §Perf cell 1).
    `group_projections` fuses same-input families (attention and mLSTM
    wq/wk/wv -> "wqkv", MLA wq/wkv_a -> "wq_kva", gate/up -> "gu") into
    single wide VQWeights with recorded splits — the decode path then
    runs one EVA matmul per family.

    `mesh` (a Mesh/AbstractMesh or an int model-axis shard count) makes
    grouping SHARD-AWARE: families whose member boundaries don't land on
    shard boundaries of the wide N axis stay ungrouped, so their members
    keep clean column sharding (instead of the grouped leaf falling back
    to per-layer-psum V-sharding). `report`, when given, is appended one
    dict per family decision: {"path", "family", "members", "splits",
    "grouped", "reason"}."""
    key = key if key is not None else jax.random.PRNGKey(0)
    extra = ("lm_head",) if quantize_lm_head else ()
    shards = _model_shards(mesh)

    def eligible(path, w):
        if extra and any(seg in path for seg in extra):
            return w.ndim >= 2 and w.shape[-2] >= _MIN_DIM \
                and w.shape[-1] >= _MIN_DIM
        return _eligible(path, w)

    def groupable(node, path, members, sibling):
        if path and path[-1] in _NO_GROUP_KEYS:
            return False
        if sibling not in node or not all(m in node for m in members):
            return False
        leaves = []
        for m in members:
            sub = node[m]
            if not (isinstance(sub, dict) and "w" in sub
                    and not isinstance(sub["w"], VQWeight)
                    and eligible(path + (m,), sub["w"])):
                return False
            leaves.append(sub["w"])
        # one shared codebook set needs identical (lead..., K) shapes
        if any(l.shape[:-1] != leaves[0].shape[:-1] for l in leaves):
            return False
        has_b = [("b" in node[m]) for m in members]
        return all(has_b) or not any(has_b)

    def group(node, path):
        """Replace groupable families in a dict with single wide leaves."""
        out = dict(node)
        for members, gkey, sibling in _GROUP_FAMILIES:
            if not groupable(out, path, members, sibling):
                continue
            splits = tuple(int(out[m]["w"].shape[-1]) for m in members)
            if not splits_shard_aligned(splits, sum(splits), shards):
                # shard-aware grouping: a misaligned family would lose
                # clean column sharding (V-sharding fallback, per-layer
                # psum) — keep the members separate on this mesh
                if report is not None:
                    report.append({
                        "path": "/".join(path), "family": gkey,
                        "members": members, "splits": splits,
                        "grouped": False,
                        "reason": f"member boundaries not aligned to "
                                  f"{shards} model-axis shards "
                                  f"(N={sum(splits)})",
                    })
                continue
            if report is not None:
                report.append({
                    "path": "/".join(path), "family": gkey,
                    "members": members, "splits": splits, "grouped": True,
                    "reason": "aligned" if shards > 1 else "unsharded",
                })
            wcat = _concat_cols([out[m]["w"] for m in members])
            grouped = {"vq": _quantize_leaf(wcat, cfg, method, key,
                                            splits=splits)}
            if "b" in out[members[0]]:
                grouped["b"] = _concat_cols([out[m]["b"] for m in members])
            for m in members:
                del out[m]
            out[gkey] = grouped
        return out

    def walk(node, path):
        if isinstance(node, dict):
            if "vq" in node:
                # already quantized (grouped this pass, or a prior pass):
                # leave the node — incl. its bias dtype — untouched, same
                # as the ungrouped replacement branch below
                return node
            if "w" in node and not isinstance(node["w"], VQWeight) \
                    and eligible(path, node["w"]):
                new = {kk: vv for kk, vv in node.items() if kk != "w"}
                new["vq"] = _quantize_leaf(node["w"], cfg, method, key)
                return new
            if group_projections:
                node = group(node, path)
            return {kk: walk(vv, path + (kk,)) for kk, vv in node.items()}
        if serving_bf16 and not isinstance(node, VQWeight):
            return _to_serving_dtype(node)
        return node

    return walk(params, ())


# ---------------------------------------------------------------------------
# KV-VQ codebook attachment (serving-time KV cache compression)
# ---------------------------------------------------------------------------
#
# KV codebooks live in the PARAM tree, one node per attention layer
# (stacked with the scanned layer params), NOT in the cache: every cache
# leaf is zero-initialized, slot-sliced and block-scattered by the
# serving memory layer (serve/paging.py), which would corrupt resident
# codebooks. Attached under the attention param dict as
#   p["kv_cb"] = {"k": (L, Hk, R, 256, vec_d), "v": ...}        (GQA)
#   p["kv_cb"] = {"lat": (L, 1, R, 256, vec_d)}                 (MLA latent)
# so the layer scan hands each layer its own (Hk, R, 256, vec_d) slice
# and models/common.attention_fwd can encode at cache-append time.

# cache-subtree name for each param-tree layer-stack segment
_KV_STACK_SEGMENTS = {"layers": "body", "pre_layers": "pre"}


def _is_gqa_attn_node(node: Any, path: Tuple[str, ...]) -> bool:
    return (isinstance(node, dict) and "wo" in node
            and ("wq" in node or "wqkv" in node) and "wkv_b" not in node
            and (not path or path[-1] not in _NO_GROUP_KEYS))


def _is_mla_attn_node(node: Any) -> bool:
    return isinstance(node, dict) and "wkv_b" in node


def _node_lead(node: dict) -> Tuple[int, ...]:
    """Stacked leading dims of an attention param node (scan layers)."""
    anchor = node["wo"] if "wo" in node else node["wkv_b"]
    if "vq" in anchor:
        return tuple(anchor["vq"].idx.shape[:-3])
    return tuple(anchor["w"].shape[:-2])


def attach_kv_codebooks(params: Any, cfg: "ModelConfig", kvq: KVQuantConfig,
                        *, codebooks: Optional[Any] = None) -> Any:
    """Attach per-layer KV codebooks to every attention param node.

    Args:
      params: model params (fp or already VQ-quantized — detection keys
        survive both).
      cfg: the ModelConfig (supplies num_kv_heads / head_dim /
        kv_lora_rank geometry).
      kvq: frozen KV-VQ geometry/variant.
      codebooks: optional calibrated codebook tree from
        ``calibrate_kv_codebooks`` keyed like the cache
        ({"body": {"k": (L, Hk, R, 256, vd), ...}, "pre": ...}); when
        None every layer gets the deterministic ``kv_grid_codebooks``
        lattice (calibration-free default).

    Returns:
      A new param tree with ``kv_cb`` nodes attached (idempotent:
      existing ``kv_cb`` nodes are replaced).

    Raises:
      ValueError: when head_dim / kv_lora_rank is not divisible by the
        config's vec_d.
    """
    def build(num_heads: int, dim: int, lead: Tuple[int, ...],
              fitted: Optional[jax.Array]) -> jax.Array:
        if fitted is not None:
            return fitted  # already (L, Hk, R, E, vd)
        cb = kv_grid_codebooks(num_heads, dim, kvq)
        return jnp.broadcast_to(cb, lead + cb.shape)

    def walk(node, path, stack):
        if not isinstance(node, dict):
            return node
        seg = _KV_STACK_SEGMENTS.get(path[-1]) if path else None
        stack = seg or stack
        fitted = (codebooks or {}).get(stack) if stack else None
        if _is_gqa_attn_node(node, path):
            lead = _node_lead(node)
            out = dict(node)
            out["kv_cb"] = {
                "k": build(cfg.num_kv_heads, cfg.head_dim, lead,
                           (fitted or {}).get("k")),
                "v": build(cfg.num_kv_heads, cfg.head_dim, lead,
                           (fitted or {}).get("v")),
            }
            return out
        if _is_mla_attn_node(node):
            lead = _node_lead(node)
            out = dict(node)
            out["kv_cb"] = {
                "lat": build(1, cfg.kv_lora_rank, lead,
                             (fitted or {}).get("lat")),
            }
            return out
        return {k: walk(v, path + (k,), stack) for k, v in node.items()}

    return walk(params, (), None)


def attach_vq_logits_head(params: Any, kc: int, *, key=None,
                          iters: int = 20) -> Any:
    """Replace the dense LM head with a VQ-Logits compressed head
    (``core.logits_vq``): the ``{"w": (D, V)}`` node under ``lm_head``
    becomes ``{"vql": VQLogitsHead}``, fitted by k-means over the head's
    scale-normalized columns. Idempotent: an already-attached head is
    re-fitted from its implied dense weight.

    Raises:
      ValueError: when params carry no separate ``lm_head`` node
        (tied-embedding models score through the embedding table) or the
        head is weight-VQ quantized (compress one family at a time).
    """
    from repro.core import logits_vq as lvq

    if not (isinstance(params, dict)
            and isinstance(params.get("lm_head"), dict)):
        raise ValueError(
            "attach_vq_logits_head: params have no lm_head node "
            "(tie_embeddings models have no separate head to compress)")
    node = params["lm_head"]
    if "vql" in node:
        w = lvq.expand(node["vql"])
    elif "vq" in node:
        raise ValueError(
            "attach_vq_logits_head: lm_head is weight-VQ quantized; "
            "attach the logits head before quantize_lm_head, not after")
    else:
        w = node["w"]
    if key is None:
        key = jax.random.PRNGKey(0)
    head = lvq.fit_logits_vq(key, w, kc, iters=iters)
    out = dict(params)
    out["lm_head"] = {"vql": head}
    return out


def kv_codebook_tree(params: Any) -> Dict[str, Any]:
    """Collect attached ``kv_cb`` nodes keyed by cache subtree name
    ({"body": {...}, "pre": {...}}) — the layout
    ``serve/kvcache.encode_prefill_cache`` consumes.

    Raises:
      ValueError: when params carry no kv_cb nodes (attach first)."""
    out: Dict[str, Any] = {}

    def walk(node, stack):
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            if k == "kv_cb" and stack:
                out[stack] = v
            else:
                walk(v, _KV_STACK_SEGMENTS.get(k, stack))

    walk(params, None)
    if not out:
        raise ValueError("params carry no kv_cb nodes "
                         "(run attach_kv_codebooks first)")
    return out


def calibrate_kv_codebooks(model: Any, params: Any, batch: Dict[str, Any],
                           kvq: KVQuantConfig, *,
                           key: Optional[jax.Array] = None) -> Dict[str, Any]:
    """Fit per-layer/per-head KV codebooks from calibration prompts.

    Runs one fp prefill of ``batch`` (e.g. {"tokens": (B, S)}) and
    k-means-fits each layer's K/V (or MLA latent) distribution through
    ``core.vq.fit_kv_codebooks``.

    Returns:
      A codebook tree for ``attach_kv_codebooks(codebooks=...)``:
      {"body": {"k": (L, Hk, R, 256, vec_d), "v": ...}, "pre": ...}
      (MLA subtrees carry {"lat": (L, 1, R, 256, vec_d)}).
    """
    from repro.models.common import RunConfig  # local: avoid import cycle

    key = key if key is not None else jax.random.PRNGKey(0)
    rc = RunConfig(mode="prefill", remat=False, attn_chunk=16)
    _, cache = model.prefill(params, batch, rc)

    def fit_stack(samples: jax.Array, k_: jax.Array) -> jax.Array:
        # samples: (L, T, Hk, dim) -> (L, Hk, R, E, vd)
        keys = jax.random.split(k_, samples.shape[0])
        return jax.lax.map(
            lambda a: fit_kv_codebooks(a[0], a[1], kvq), (keys, samples))

    out: Dict[str, Any] = {}
    for name, node in cache.items():
        if not isinstance(node, dict):
            continue
        L = jax.tree_util.tree_leaves(node)[0].shape[0]
        if "k" in node and "v" in node:
            k_smp = node["k"].reshape(L, -1, *node["k"].shape[-2:])
            v_smp = node["v"].reshape(L, -1, *node["v"].shape[-2:])
            key, k1, k2 = jax.random.split(key, 3)
            out[name] = {"k": fit_stack(k_smp, k1),
                         "v": fit_stack(v_smp, k2)}
        elif "latent" in node:
            lat = node["latent"]
            lat_smp = lat.reshape(L, -1, 1, lat.shape[-1])
            key, k1 = jax.random.split(key)
            out[name] = {"lat": fit_stack(lat_smp, k1)}
    if not out:
        raise ValueError("prefill cache carries no quantizable KV nodes")
    return out


def count_vq_layers(params: Any) -> int:
    n = 0

    def walk(node):
        nonlocal n
        if isinstance(node, dict):
            if "vq" in node:
                n += 1
            for v in node.values():
                walk(v)

    walk(params)
    return n


def compressed_model_bytes(params: Any) -> Tuple[int, int]:
    """Returns (vq_bytes, dense_bytes_bf16_equivalent) over VQ'd leaves."""
    vq_b, dense_b = 0, 0

    def walk(node):
        nonlocal vq_b, dense_b
        if isinstance(node, dict):
            if "vq" in node:
                v: VQWeight = node["vq"]
                lead = int(np.prod(v.idx.shape[:-3])) if v.idx.ndim > 3 else 1
                vq_b += lead * v.compressed_bytes()
                dense_b += lead * v.K * v.N * 2
            for x in node.values():
                if isinstance(x, dict):
                    walk(x)

    walk(params)
    return vq_b, dense_b
