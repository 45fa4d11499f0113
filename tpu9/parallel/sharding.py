"""Sharding rules for the model param trees.

Megatron-style TP layout for the decoder (column-parallel up-projections,
row-parallel down-projections so each layer needs one all-reduce per block),
optionally combined with FSDP sharding of the remaining dimension. GSPMD
inserts the collectives; these specs are the whole "distributed backend".

Layout table (decoder params from tpu9.models.transformer):

  embed   [V, D]   P(fsdp, None)        (vocab rows sharded by fsdp)
  lm_head [D, V]   P(fsdp, tp)          (column-parallel logits)
  wq/wk/wv[D, HDh] P(fsdp, tp)          (column-parallel heads)
  wo      [HDh, D] P(tp, fsdp)          (row-parallel → psum)
  w_gate  [D, F]   P(fsdp, tp)
  w_up    [D, F]   P(fsdp, tp)
  w_down  [F, D]   P(tp, fsdp)
  norms   [D]      replicated
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = dict[str, Any]


def _quant_aware(spec: P, leaf) -> Any:
    """int8-quantized weights are {"q": [in,out] int8, "scale": [1,out]} —
    shard q like the dense weight and scale along the output axis."""
    if isinstance(leaf, dict) and "q" in leaf:
        out_axis = spec[1] if len(spec) > 1 else None
        return {"q": spec, "scale": P(None, out_axis)}
    return spec


def _layer_specs(layer: Params, tp: str, fsdp: Optional[str],
                 moe_axis: Optional[str] = None) -> dict:
    base = {
        "attn_norm": P(),
        "mlp_norm": P(),
        "attn_post_norm": P(),      # a sandwich-norm layer's other two
        "mlp_post_norm": P(),
        "summary_mu": P(),          # [KH, D] a layer: replicated like a norm
        "summary_phi": P(),
        "q_norm": P(),              # [D] a head (``DecoderConfig.qk_norm``)
        "k_norm": P(),
        "wq": P(fsdp, tp),
        "wk": P(fsdp, tp),
        "wv": P(fsdp, tp),
        "wo": P(tp, fsdp),
        "w_gate": P(fsdp, tp),
        "w_up": P(fsdp, tp),
        "w_down": P(tp, fsdp),
    }
    out = {name: _quant_aware(spec, layer.get(name))
           for name, spec in base.items() if name in layer}
    for kind in ("kda", "mla", "ssm", "conv"):
        # a layer pattern's attention (``models.hybrid``, ``models.ssm``,
        # ``models.shortconv``): one
        # chip's, a mesh is refused with it — every leaf replicated
        if kind in layer:
            out[kind] = replicate_specs(layer[kind])
    if "moe" in layer:
        # mixtral layers: the expert (leading) dim shards over ``moe_axis``
        # — "tp" by default so a plain tp/fsdp serving mesh works; pass
        # moe_axis="ep" to decoder_param_specs on ep meshes. shard_params
        # replicates instead when n_experts isn't divisible by the axis
        # size (e.g. 8 experts on tp=16). One source of truth: moe.py.
        from ..models.moe import moe_param_specs
        out["moe"] = moe_param_specs(layer["moe"], axis=moe_axis or tp)
    return out


def decoder_param_specs(params: Params, tp: str = "tp",
                        fsdp: Optional[str] = "fsdp",
                        moe_axis: Optional[str] = None) -> Params:
    """PartitionSpec tree matching a decoder param tree (dense, int8-
    quantized, or MoE — expert dims shard over ``moe_axis``, default tp)."""
    specs: Params = {
        "embed": P(fsdp, None),
        "final_norm": P(),
        "layers": [_layer_specs(layer, tp, fsdp, moe_axis=moe_axis)
                   for layer in params["layers"]],
    }
    if "lm_head" in params:
        specs["lm_head"] = _quant_aware(P(fsdp, tp), params["lm_head"])
    if "exit_gate" in params:       # [D] + bias: replicated like a norm
        specs["exit_gate"] = {"w": P(), "b": P()}
    return specs


def fsdp_specs(params: Params, axis: str = "fsdp",
               min_size: int = 2 ** 14) -> Params:
    """Generic FSDP rule for any pytree: shard the largest divisible dim of
    every big tensor along ``axis``; small tensors replicate. Used for
    adapter/optimizer trees where no TP layout applies."""

    def rule(x):
        if not hasattr(x, "shape") or x.size < min_size or x.ndim == 0:
            return P()
        dims = [None] * x.ndim
        largest = max(range(x.ndim), key=lambda i: x.shape[i])
        dims[largest] = axis
        return P(*dims)

    return jax.tree_util.tree_map(rule, params)


def replicate_specs(tree: Params) -> Params:
    return jax.tree_util.tree_map(lambda _: P(), tree)


def shard_params(params: Params, mesh: Mesh, specs: Params) -> Params:
    """Device-put a param tree according to a spec tree. Dims not divisible by
    the mesh axis fall back to replication on that dim (keeps tiny test models
    working on any mesh)."""

    def place(x, spec):
        if not hasattr(x, "shape"):
            return x
        fixed = fit_spec(x.shape, spec, mesh)
        return jax.device_put(x, NamedSharding(mesh, fixed))

    return jax.tree_util.tree_map(place, params, specs,
                                  is_leaf=lambda x: isinstance(x, P))


def fit_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop spec axes whose mesh size does not divide the dim (replicate
    that dim instead) — the divisibility fallback ``shard_params`` applies,
    exposed for callers that build NamedShardings directly (the serving
    sharding policy sizes KV pools with it)."""
    ndim = len(shape)
    dims = []
    for i, axis in enumerate(spec):
        if axis is None or i >= ndim:
            dims.append(None)
            continue
        if isinstance(axis, str):
            size = mesh.shape[axis]
        elif isinstance(axis, (tuple, list)):
            # multi-axis entries like P(("tp", "fsdp")) shard over the
            # PRODUCT of the axes — sizing them as 1 would skip the
            # divisibility fallback and crash device_put instead of
            # replicating gracefully
            size = 1
            for a in axis:
                size *= mesh.shape[a]
        else:
            size = 1
        dims.append(axis if shape[i] % size == 0 else None)
    while len(dims) < ndim:
        dims.append(None)
    return P(*dims)


# back-compat private alias (array-taking form)
def _fit_spec(x, spec: P, mesh: Mesh) -> P:
    return fit_spec(x.shape, spec, mesh)


def constrain(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """Activation sharding hint inside jit. The mesh is the one the caller
    entered with ``jax.set_mesh``; outside any it is a no-op — but a BAD
    spec must still raise: swallowing an axis-name typo would silently
    drop the layout hint and ship a perf/memory regression."""
    if jax.sharding.get_abstract_mesh().empty:
        return x                     # genuinely outside any mesh context
    return jax.lax.with_sharding_constraint(x, spec)
