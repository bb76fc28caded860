"""The Partitioner: one object that owns every placement decision.

Before this subsystem, sharding decisions were scattered — raw
``NamedSharding`` literals in ``train/finetune.py``, an ad-hoc dp mesh
inside ``BatchedRunner``, device pinning inside ``ReplicaPool`` — and
anything beyond pure data parallelism meant editing all of them. A
:class:`Partitioner` centralizes the decisions behind one surface
(mirroring the ``DataParallelPartitioner``/``SPMDPartitioner`` split of
the exemplar codebases, SNIPPETS [2]):

- **where a batch goes** (:meth:`shard_batch` / :meth:`batch_sharding`),
- **where params and optimizer state live** (:meth:`shard_params` /
  :meth:`shard_opt_state`, specs from the regex rule tables of
  ``partition/rules.py`` and the ZeRO policy of ``partition/zero.py``),
- **how a step is compiled** (:meth:`wrap_step` pins the output state to
  its shardings from *inside* the traced function, so the same wrapped
  step works under plain ``jit`` and under ``chain_carry``'s scan; and
  :meth:`wrap_apply` jits an inference forward with **explicit
  in/out shardings**),
- **how state leaves the mesh** (:meth:`gather_for_checkpoint`).

Implementations:

- :class:`SingleDevicePartitioner` — no mesh; everything on one pinned
  (or the default) device. What a ``ReplicaPool`` executor uses.
- :class:`DataParallelPartitioner` — batch split over the data axes,
  params replicated; ``zero_axis="fsdp"`` additionally ZeRO-shards the
  optimizer state (per-chip opt memory ~1/fsdp, arXiv 2004.13336).
- :class:`SPMDPartitioner` — params placed by a rule table (tp/fsdp),
  batch over the data axes; the general dp × tp × fsdp form.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkdl_tpu.partition.mesh_factory import axis_sizes
from sparkdl_tpu.partition.rules import (
    match_partition_rules,
    tree_path_names,
)
from sparkdl_tpu.partition.zero import (
    export_opt_state_bytes,
    zero_partition_specs,
)
from sparkdl_tpu.runtime.mesh import MeshShapeError

__all__ = [
    "Partitioner",
    "SingleDevicePartitioner",
    "DataParallelPartitioner",
    "SPMDPartitioner",
]


def _unbox(tree: Any) -> Any:
    """Strip flax ``nn.Partitioned`` boxes if flax is in the tree."""
    try:
        from flax.core import meta
    except Exception:  # pragma: no cover - flax is a hard dep in practice
        return tree
    return meta.unbox(tree)


class Partitioner:
    """Base class: a mesh (possibly None), the batch axes, and the spec
    policies. Subclasses override the ``*_specs`` policy hooks; the
    placement/compile mechanics live here once."""

    def __init__(self, mesh: "Mesh | None" = None, *,
                 batch_axes: Sequence[str] = ("dp", "fsdp"),
                 zero_axis: "str | None" = None):
        self.mesh = mesh
        if mesh is not None:
            missing = [a for a in batch_axes if a not in mesh.axis_names]
            if missing:
                raise MeshShapeError(
                    f"batch axes {missing} not in mesh axes "
                    f"{tuple(mesh.axis_names)}"
                )
            if zero_axis is not None and zero_axis not in mesh.axis_names:
                raise MeshShapeError(
                    f"zero_axis {zero_axis!r} not in mesh axes "
                    f"{tuple(mesh.axis_names)}"
                )
        self.batch_axes = tuple(batch_axes)
        self.zero_axis = zero_axis
        # NamedShardings are immutable; cache them per spec so hot paths
        # (one shard_batch per dispatch) never rebuild one
        self._sharding_cache: "dict[P, NamedSharding]" = {}

    # -- spec policy hooks ---------------------------------------------------
    def batch_spec(self) -> P:
        """Leading (batch) dim split over the data axes."""
        return P(self.batch_axes)

    def param_specs(self, params: Any, *, count_hits: bool = False) -> Any:
        """Pytree of ``PartitionSpec`` for the params. Replicated here;
        :class:`SPMDPartitioner` consults its rule table.
        ``count_hits`` lands matches in the rule-hit metric — only
        :meth:`shard_params` (the authoritative placement) sets it, so
        ``sparkdl_partition_rule_hits_total`` counts each placement
        once no matter how many derived views (``wrap_apply``,
        ``param_shardings``) re-ask for the specs."""
        del count_hits
        return jax.tree_util.tree_map(lambda _: P(), _unbox(params))

    def opt_specs(self, opt_state: Any, *, count_hits: bool = False) -> Any:
        """Specs for the optimizer state: the param rules re-matched over
        the state's paths (the state mirrors the param tree), then — with
        ``zero_axis`` set — ZeRO-sharded along that axis wherever still
        replicated (partition/zero.py)."""
        base = self._opt_base_specs(opt_state, count_hits=count_hits)
        if self.zero_axis is None:
            return base
        return zero_partition_specs(
            opt_state, axis=self.zero_axis,
            axis_size=self._axis_size(self.zero_axis), base_specs=base,
        )

    def _opt_base_specs(self, opt_state: Any, *,
                        count_hits: bool = False) -> Any:
        del count_hits
        return jax.tree_util.tree_map(lambda _: P(), opt_state)

    # -- derived shardings ---------------------------------------------------
    def _named(self, spec: P) -> "NamedSharding":
        assert self.mesh is not None
        cached = self._sharding_cache.get(spec)
        if cached is None:
            cached = self._sharding_cache[spec] = NamedSharding(
                self.mesh, spec)
        return cached

    def batch_sharding(self) -> "NamedSharding":
        return self._named(self.batch_spec())

    def chain_batch_sharding(self) -> "NamedSharding":
        """For a stacked ``[K, batch, ...]`` fused-dispatch feed: K is the
        scanned dim (unsharded), batch stays on the data axes."""
        return self._named(P(None, self.batch_axes))

    def replicated_sharding(self) -> "NamedSharding":
        return self._named(P())

    def param_shardings(self, params: Any) -> Any:
        return jax.tree_util.tree_map(
            self._named, self.param_specs(params))

    def opt_shardings(self, opt_state: Any) -> Any:
        return jax.tree_util.tree_map(self._named, self.opt_specs(opt_state))

    # -- placement -----------------------------------------------------------
    def shard_batch(self, arrays: Any, *, check: bool = True) -> Any:
        """Host batch -> device, split over the data axes. Loud on a
        batch dim the mesh cannot divide (the alternative is an XLA
        error naming nothing). ``check=False`` skips the per-leaf walk
        for dispatch paths whose batches are already padded to
        data-axis multiples (BatchedRunner's bucketed feed)."""
        n = self.data_axis_size
        if check and n > 1:
            for name, leaf in tree_path_names(arrays):
                dim = getattr(leaf, "shape", (0,))
                if dim and dim[0] % n != 0:
                    raise MeshShapeError(
                        f"batch leaf {name!r} has leading dim {dim[0]}, "
                        f"not divisible by the {n}-way data axes "
                        f"{self.batch_axes} of the "
                        f"{self.mesh.devices.size}-device mesh"
                    )
        return jax.device_put(arrays, self.batch_sharding())

    @staticmethod
    def _owned_put(tree: Any, shardings: Any) -> Any:
        """Place ``tree`` on ``shardings`` with buffers the RESULT owns.

        Train state is DONATED on the fused-dispatch path (chain_carry),
        and ``device_put`` may alias same-device shards — donation
        would then delete the caller's own arrays. A jitted identity
        with ``out_shardings`` always materializes fresh buffers."""
        return jax.jit(lambda t: t, out_shardings=shardings)(tree)

    def shard_params(self, params: Any) -> Any:
        # the one placement that counts rule hits: specs derived ONCE
        # and reused for validation + sharding, so
        # sparkdl_partition_rule_hits_total is one count per placement
        params = _unbox(params)
        specs = self.param_specs(params, count_hits=True)
        self._check_divisible(params, specs, "param")
        return self._owned_put(
            params, jax.tree_util.tree_map(self._named, specs))

    def shard_opt_state(self, opt_state: Any) -> Any:
        specs = self.opt_specs(opt_state, count_hits=True)
        self._check_divisible(opt_state, specs, "opt")
        return self._owned_put(
            opt_state, jax.tree_util.tree_map(self._named, specs))

    def shard_replicated(self, tree: Any) -> Any:
        """Place small fully-replicated leaves (step counters, schedules)."""
        return self._owned_put(tree, jax.tree_util.tree_map(
            lambda _: self.replicated_sharding(), tree))

    def gather_for_checkpoint(self, tree: Any) -> Any:
        """Fully-replicated copy of ``tree`` on the same mesh — what a
        layout-independent checkpoint (or a host export) wants. The
        :class:`~sparkdl_tpu.checkpoint.CheckpointManager` also saves
        sharded trees directly (orbax records the layout); gathering
        first buys a checkpoint any future partitioner can restore
        without resharding metadata."""
        repl = self.replicated_sharding()
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, repl), _unbox(tree))

    # -- compile -------------------------------------------------------------
    def wrap_step(self, step_fn: Callable, state_shardings: Any) -> Callable:
        """``(state, batch) -> (state, aux)`` with the output state
        constrained to ``state_shardings`` from inside the trace.

        The constraint — not ``out_shardings`` — is what keeps ZeRO
        state sharded across steps on every compile path: it survives
        ``jax.jit``, ``chain_carry``'s ``lax.scan``, and donation
        unchanged, because it is part of the traced computation itself.
        """

        def wrapped(state, batch):
            new_state, aux = step_fn(state, batch)
            return (
                lax.with_sharding_constraint(new_state, state_shardings),
                aux,
            )

        return wrapped

    def wrap_apply(self, apply_fn: Callable, params: Any) -> Callable:
        """Jit ``apply_fn(params, batch)`` with **explicit** in/out
        shardings: params on their specs, batch and every output leaf
        split over the data axes."""
        return jax.jit(
            apply_fn,
            in_shardings=(self.param_shardings(_unbox(params)),
                          self.batch_sharding()),
            out_shardings=self.batch_sharding(),
        )

    # -- introspection / context ---------------------------------------------
    def _axis_size(self, axis: str) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[axis])

    @property
    def data_axis_size(self) -> int:
        """Ways the batch dim is split (1 = no splitting)."""
        n = 1
        for a in self.batch_axes:
            n *= self._axis_size(a)
        return n

    def mesh_context(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def describe(self) -> "dict[str, Any]":
        """Operator/bench view: kind, axis sizes, batch/zero policy."""
        return {
            "kind": type(self).__name__,
            "axes": axis_sizes(self.mesh),
            "batch_axes": list(self.batch_axes),
            "zero_axis": self.zero_axis,
            "data_axis_size": self.data_axis_size,
        }

    def export_opt_state_bytes(self, opt_state: Any) -> int:
        """Per-chip optimizer-state bytes into the spine
        (``sparkdl_opt_state_bytes{axis=...}``)."""
        return export_opt_state_bytes(opt_state, axis=self.zero_axis)

    # -- validation ----------------------------------------------------------
    def _check_divisible(self, tree: Any, specs: Any, what: str) -> None:
        if self.mesh is None:
            return
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for (name, leaf), spec in zip(tree_path_names(tree), spec_leaves):
            shape = tuple(getattr(leaf, "shape", ()))
            for i, part in enumerate(spec):
                if part is None or i >= len(shape):
                    continue
                entries = part if isinstance(part, (tuple, list)) else (part,)
                n = 1
                for a in entries:
                    n *= self._axis_size(a)
                if n > 1 and shape[i] % n != 0:
                    raise MeshShapeError(
                        f"{what} leaf {name!r} shape {shape}: dim {i} "
                        f"({shape[i]}) not divisible by the {n}-way "
                        f"{entries} split on the "
                        f"{self.mesh.devices.size}-device mesh"
                    )


class SingleDevicePartitioner(Partitioner):
    """Everything on one device (the given one, or jax's default).

    The degenerate-but-load-bearing case: a :class:`~sparkdl_tpu.serving.
    replicas.ReplicaPool` executor is exactly this — the pool scales by
    replicating single-device partitioners, not by splitting batches."""

    def __init__(self, device: Any = None):
        super().__init__(mesh=None, batch_axes=())
        self.device = device

    def batch_spec(self) -> P:
        return P()

    def _named(self, spec: P) -> Any:
        # no mesh: every derived "sharding" (batch/chain/replicated/param)
        # is the one device — keeps the whole base-class surface
        # (finetune's batch_sharding()/chain_batch_sharding() included)
        # usable instead of tripping the mesh assert
        device = self.device
        if device is None:
            device = jax.local_devices()[0]
        return jax.sharding.SingleDeviceSharding(device)

    def shard_batch(self, arrays: Any, *, check: bool = True) -> Any:
        # plain put: batches are never donated, so aliasing is safe here
        # (params/opt state go through the base class's _owned_put —
        # a device_put-aliased TrainState donated by chain_carry would
        # delete the caller's own arrays)
        if self.device is None:
            return jax.device_put(arrays)
        return jax.device_put(arrays, self.device)

    def gather_for_checkpoint(self, tree: Any) -> Any:
        return _unbox(tree)

    def wrap_step(self, step_fn: Callable,
                  state_shardings: Any = None) -> Callable:
        return step_fn  # nothing to constrain on one device

    def wrap_apply(self, apply_fn: Callable, params: Any) -> Callable:
        jitted = jax.jit(apply_fn)
        if self.device is None:
            return jitted
        return lambda p, batch: jitted(
            jax.device_put(p, self.device), self.shard_batch(batch))

    def describe(self) -> "dict[str, Any]":
        out = super().describe()
        out["device"] = str(self.device) if self.device is not None else None
        return out


class DataParallelPartitioner(Partitioner):
    """Batch over the data axes, params replicated — the reference-parity
    layout, now with an optional ZeRO twist: ``zero_axis="fsdp"`` shards
    the optimizer state (and therefore the weight-update math) along the
    fsdp axis while params stay replicated. Per-chip opt memory drops
    ~fsdp-fold; the update all-gather is XLA's to place and overlap."""

    def __init__(self, mesh: "Mesh | None" = None, *,
                 batch_axes: Sequence[str] = ("dp", "fsdp"),
                 zero_axis: "str | None" = None):
        if mesh is None:
            from sparkdl_tpu.runtime.mesh import data_parallel_mesh

            mesh = data_parallel_mesh()
        super().__init__(mesh, batch_axes=batch_axes, zero_axis=zero_axis)


class SPMDPartitioner(Partitioner):
    """General dp × tp × fsdp: params placed by a regex rule table
    (partition/rules.py), batch over the data axes, optimizer state
    rule-matched the same way (the state's paths contain the param
    paths) plus ZeRO sharding along ``zero_axis`` where replicated."""

    def __init__(self, mesh: Mesh, rules: "Sequence[tuple[str, P]]", *,
                 batch_axes: Sequence[str] = ("dp", "fsdp"),
                 zero_axis: "str | None" = None):
        super().__init__(mesh, batch_axes=batch_axes, zero_axis=zero_axis)
        self.rules = tuple(rules)

    def param_specs(self, params: Any, *, count_hits: bool = False) -> Any:
        return match_partition_rules(
            self.rules, _unbox(params), count_hits=count_hits)

    def _opt_base_specs(self, opt_state: Any, *,
                        count_hits: bool = False) -> Any:
        return match_partition_rules(
            self.rules, opt_state, count_hits=count_hits)

    def describe(self) -> "dict[str, Any]":
        out = super().describe()
        out["n_rules"] = len(self.rules)
        return out
