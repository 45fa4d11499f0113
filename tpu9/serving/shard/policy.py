"""Sharding policy objects: how the serving engine places and constrains
its device state on a submesh (ISSUE 9 tentpole).

The engine is topology-OBLIVIOUS: every device placement it performs goes
through one of these hooks, and the single-device policy makes every hook
the identity — so a ``1x1`` engine traces exactly the graphs a
policy-free engine would (bit-identical compile keys, no constraint ops
inserted). :class:`MeshPolicy` is where multichip serving actually lives:

- weights placed by ``parallel.sharding.decoder_param_specs`` (Megatron
  column/row TP × FSDP, quantization-aware);
- the paged KV pool ``[L, N, BS, KH, D]`` sharded on the HEAD axis over
  ``tp`` (the block/position axes stay replicated-indexable, so the
  host-side block allocator, prefix cache and admission accounting are
  untouched — block ids are global, only the resident layout is sharded);
  int8 scale planes ``[L, N, BS, KH]`` shard identically so every write
  shares the table math;
- activations/pool outputs pinned with ``with_sharding_constraint`` at
  graph boundaries, so donation round-trips the pool without GSPMD ever
  deciding to gather it.

The dtype boundary stays where ISSUE 6 put it (ops.quant + the engine's
pool writers); this module only ever sees shapes.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .plan import Topology

Params = dict[str, Any]

# KV-array sharding rules by array name; rank tells payload from scale
# planes. Table rows are host-produced global block ids — replicated.
_HEAD_AXIS = "tp"


class SingleDevicePolicy:
    """The identity policy: today's single-chip engine, verbatim. Every
    hook returns its input unchanged (``zeros`` is a plain ``jnp.zeros``)
    so no sharding machinery exists anywhere near the traced graphs."""

    topology = Topology(1, 1)
    mesh = None

    def describe(self) -> dict:
        return self.topology.as_dict()

    # -- placement -----------------------------------------------------------

    def place_params(self, params: Params) -> Params:
        return params

    def build_params(self, init, rng) -> Params:
        """``init(rng)`` with every leaf built where :meth:`place_params`
        would put it."""
        return init(rng)

    def place_kv(self, tree: Params) -> Params:
        return tree

    def zeros(self, shape, dtype, name: str = "") -> jnp.ndarray:
        return jnp.zeros(shape, dtype)

    def device_table(self, table_np: np.ndarray) -> jnp.ndarray:
        # a SNAPSHOT: the host goes on writing rows into ``table_np``, and
        # the CPU backend may read a numpy buffer where it lies — a window
        # still in flight would then see a row that became real after its
        # dispatch, and its idle lane would write over the row's first page
        return jnp.asarray(table_np.copy())

    # -- traced-graph hooks --------------------------------------------------

    def constrain_kv(self, tree: Params) -> Params:
        return tree

    # -- kvwire gather (ISSUE 16) --------------------------------------------

    def gather_kv(self, name: str, arr) -> np.ndarray:
        """Canonical full-head HOST copy of one pool array — the kvwire
        export gather. ``device_get`` on a head-sharded mesh array
        assembles the global array (single-process mesh), so a tp=2
        exporter emits byte-identical planes to a tp=1 one and import
        re-places through :meth:`place_kv`. Off the serve loop by
        construction (exports run between windows)."""
        return np.asarray(jax.device_get(arr))  # tpu9: noqa[JAX001] kvwire export / window-boundary down-page gather — never on the per-token path

    # -- spec introspection (graphcheck — ISSUE 11) --------------------------
    # The declared layout contract, exposed so the static verifier can
    # check lowered graphs against it without groping mesh internals. On
    # the identity policy every spec is None: nothing is sharded, and a
    # verifier must expect NO constraint ops in the traced graphs.

    def kv_spec(self, name: str, ndim: int):
        """PartitionSpec this policy pins KV-state array ``name`` (rank
        ``ndim``) to, or None when the policy places nothing."""
        return None

    def param_specs(self, tree: Any):
        """``(declared, resolved)`` PartitionSpec trees for a param tree:
        ``declared`` is the raw layout rule (Megatron TP×FSDP) and
        ``resolved`` what actually shards after the divisibility
        fallback — a leaf sharded in ``declared`` but replicated in
        ``resolved`` is the silent-replication case graphcheck flags.
        ``(None, None)`` on the identity policy."""
        return None, None

    # -- abstract (compile-ahead) --------------------------------------------

    def abstract(self, tree: Any, kv: bool = False) -> Any:
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    # -- observability -------------------------------------------------------

    def devices(self) -> list:
        return [jax.devices()[0]] if jax.devices() else []

    def memory_stats(self) -> list:
        """Each chip's ``memory_stats()`` in device order, one sweep —
        ``[]`` where the backend reports none (CPU)."""
        stats = [d.memory_stats() for d in self.devices()]
        return stats if all(stats) else []

    def hbm_gb_by_chip(self, key: str = "bytes_in_use",
                       stats: Optional[list] = None) -> list:
        """``key`` of each chip's memory stats, GB (``stats``: a sweep
        already taken). A mesh whose first chip holds far more than the
        rest is the everything-landed-on-device-0 placement fault,
        readable here."""
        if stats is None:
            stats = self.memory_stats()
        return [round(s.get(key, 0) / 1e9, 3) for s in stats]

    def hbm_limit_gb_per_chip(self) -> float:
        """Smallest per-chip HBM capacity across the submesh, GB — the
        denominator of the health plane's headroom gauges (ISSUE 14).
        0.0 where the backend has no memory stats (CPU)."""
        return min(self.hbm_gb_by_chip("bytes_limit"), default=0.0)


class MeshPolicy(SingleDevicePolicy):
    """Mesh-sharded placement for a tp(×fsdp) serving submesh."""

    def __init__(self, topology: Topology,
                 devices: Optional[Sequence] = None):
        from ...parallel import make_mesh
        self.topology = topology
        # tp innermost (fastest ICI links), fsdp outside — the mesh.py
        # axis convention the MULTICHIP probes validated
        self.mesh = make_mesh(dp=1, fsdp=topology.fsdp, sp=1,
                              tp=topology.tp, devices=devices)

    def describe(self) -> dict:
        return self.topology.as_dict()

    def kv_spec(self, name: str, ndim: int):
        """PartitionSpec for one KV-state array by name/rank: payloads
        ``[..., KH, D]`` and scale planes ``[..., KH]`` shard the head
        axis, where ``models.kvstate`` says it is; tables (int32 block
        ids) and state kept by lane replicate. Public: this IS the
        declared KV layout contract graphcheck verifies lowered graphs
        against (ISSUE 11)."""
        from jax.sharding import PartitionSpec as P
        from ...models.kvstate import head_axis
        axis = head_axis(name, ndim)
        if axis is None:
            return P()
        dims: list = [None] * ndim
        dims[axis] = _HEAD_AXIS
        return P(*dims)

    def param_specs(self, tree: Any):
        """Declared + divisibility-resolved weight specs (see base)."""
        from jax.sharding import PartitionSpec as P
        from ...parallel import decoder_param_specs, fit_spec
        try:
            declared = decoder_param_specs(tree)
        except (KeyError, TypeError):
            declared = jax.tree_util.tree_map(lambda _: P(), tree)
        resolved = jax.tree_util.tree_map(
            lambda a, s: (fit_spec(a.shape, s, self.mesh)
                          if hasattr(a, "shape") else s),
            tree, declared, is_leaf=lambda x: isinstance(x, P))
        return declared, resolved

    def _kv_sharding(self, name: str, shape):
        from jax.sharding import NamedSharding
        from ...parallel import fit_spec
        return NamedSharding(
            self.mesh, fit_spec(shape, self.kv_spec(name, len(shape)),
                                self.mesh))

    # -- placement -----------------------------------------------------------

    def place_params(self, params: Params) -> Params:
        from jax.sharding import PartitionSpec as P
        from ...parallel import decoder_param_specs, shard_params
        try:
            specs = decoder_param_specs(params)
        except (KeyError, TypeError):
            # non-decoder tree (custom handler model): replicate rather
            # than fail — correctness first, layout is the decoder path's
            specs = jax.tree_util.tree_map(lambda _: P(), params)
        return shard_params(params, self.mesh, specs)

    def build_params(self, init, rng) -> Params:
        # jit-with-out-shardings, as in :meth:`zeros`: each chip generates
        # only its shard. A model sharded BECAUSE it fits no single chip
        # must never be materialized on the first one.
        shardings = jax.tree_util.tree_map(
            lambda a: a.sharding, self.abstract(jax.eval_shape(init, rng)))
        build = jax.jit(init, out_shardings=shardings)
        return build(rng)

    def place_kv(self, tree: Params) -> Params:
        return {name: jax.device_put(arr,
                                     self._kv_sharding(name, arr.shape))
                for name, arr in tree.items()}

    def zeros(self, shape, dtype, name: str = "") -> jnp.ndarray:
        # jit-with-out-shardings: each chip materializes only its shard —
        # a host zeros + device_put would stage the full array through
        # device 0 (for a 31B-class pool that is the whole HBM)
        return _sharded_zeros(tuple(shape), jnp.dtype(dtype),
                              self._kv_sharding(name, shape))()

    def device_table(self, table_np: np.ndarray) -> jnp.ndarray:
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(table_np.copy()),   # as above
                              NamedSharding(self.mesh, P()))

    # -- traced-graph hooks --------------------------------------------------

    def constrain_kv(self, tree: Params) -> Params:
        """Pin KV-state outputs to their resident layout inside a traced
        graph, so the donated pool keeps its head sharding across every
        decode/verify/splice round trip."""
        return {name: jax.lax.with_sharding_constraint(
                    arr, self._kv_sharding(name, arr.shape))
                for name, arr in tree.items()}

    # -- abstract (compile-ahead) --------------------------------------------

    def abstract(self, tree: Any, kv: bool = False) -> Any:
        """ShapeDtypeStruct tree WITH shardings, so compile-ahead lowers
        the same SPMD executables the serve loop will dispatch. ``kv``
        trees use the KV rules (keyed by dict name); everything else uses
        the decoder param specs."""
        if kv:
            return {name: jax.ShapeDtypeStruct(
                        a.shape, a.dtype,
                        sharding=self._kv_sharding(name, a.shape))
                    for name, a in tree.items()}
        from jax.sharding import NamedSharding, PartitionSpec as P
        _, resolved = self.param_specs(tree)

        def one(a, spec):
            if not hasattr(a, "shape"):
                return a
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(
            one, tree, resolved, is_leaf=lambda x: isinstance(x, P))

    # -- observability -------------------------------------------------------

    def devices(self) -> list:
        return list(self.mesh.devices.flat)


@functools.lru_cache(maxsize=64)
def _sharded_zeros(shape: tuple, dtype, sharding):
    """Cached jitted sharded-zeros builder (NamedSharding hashes by mesh +
    spec): pools of one shape/layout compile their init exactly once."""
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def make_policy(topology: "Topology | str | None",
                devices: Optional[Sequence] = None) -> SingleDevicePolicy:
    """Policy for a topology: ``None``/``1x1`` → the identity policy (the
    engine stays byte-for-byte today's engine), anything larger → mesh."""
    from .plan import parse_topology
    topo = parse_topology(topology) or Topology(1, 1)
    if topo.is_single:
        return SingleDevicePolicy()
    n = len(devices) if devices is not None else len(jax.devices())
    if topo.n_chips > n:
        raise ValueError(
            f"topology {topo} needs {topo.n_chips} devices, have {n}")
    return MeshPolicy(topo, devices=devices)
