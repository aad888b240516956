"""Declarative parallelism plan — ONE source of truth for every layout.

The operator declares the topology once (``--mesh data:N,seq:M,pipe:K``)
and every consumer *derives* its shardings from the resulting
:class:`ParallelPlan` instead of hand-wiring per-leaf layouts:

- the trainer derives batch placement, param shardings, the ZeRO-1
  optimizer-state layout and the pipeline stage layout;
- the predictor and the serving engine derive batch placement;
- the HBM pre-flight and ``bench.py`` report ``plan.describe()`` and
  ``plan.unused_devices``;
- checkpoint manifests record ``mesh_axes`` so a restore knows what
  topology wrote them (reshard-on-restore stays shape-driven).

This is the TorchTitan discipline (arxiv 2410.06511): a single mesh +
per-feature sharding *derivation* is what makes 3D/4D parallelism
composable instead of five parallel rewirings. graftlint rule MLA009
enforces the flip side: no ``NamedSharding``/``PartitionSpec``
construction outside ``parallel/``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import MeshSpec, build_mesh, elastic_axes, unused_device_count
from .sharding import (
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    ZeroLeafPlan,
    batch_pspec,
    batch_sharding,
    is_single_device,
    param_pspecs,
    zero1_plan,
    zero_pspecs,
)


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """The declarative mesh plan: a named mesh plus derivation methods.

    Construction: :meth:`from_spec` (the ``--mesh`` string) or
    :meth:`from_mesh` (an already-built mesh). Both record how many
    visible devices the mesh leaves stranded.
    """

    mesh: Mesh
    unused_devices: int = 0
    # the axes the OPERATOR asked for (--mesh), recorded by
    # elastic_from_spec so `shrunk` can report a topology change; None for
    # plans built by the fixed-world constructors
    requested_axes: Optional[Dict[str, int]] = None

    # -- construction --------------------------------------------------------

    @classmethod
    def from_spec(cls, spec: Optional[str] = None, *,
                  devices: Optional[Sequence] = None) -> "ParallelPlan":
        mesh = build_mesh(spec, devices=devices)
        return cls(mesh=mesh, unused_devices=unused_device_count(mesh))

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "ParallelPlan":
        return cls(mesh=mesh, unused_devices=unused_device_count(mesh))

    @classmethod
    def elastic_from_spec(cls, spec: Optional[str] = None, *,
                          devices: Optional[Sequence] = None,
                          min_data: int = 1) -> "ParallelPlan":
        """``from_spec`` that SHRINKS instead of raising when the requested
        mesh no longer fits the live device set (``--elastic on``): only
        the data axis narrows (``mesh.elastic_axes``), structural axes
        refuse loudly. Records the original request so ``shrunk`` (and the
        mesh_shrunk flight-recorder event) can report the change."""
        devices = list(devices if devices is not None else jax.devices())
        requested = MeshSpec.from_string(spec, n_devices=len(devices)).ordered()
        axes = elastic_axes(requested, len(devices), min_data=min_data)
        mesh = build_mesh(devices=devices, axes=axes)
        return cls(
            mesh=mesh,
            unused_devices=unused_device_count(mesh),
            requested_axes=dict(requested),
        )

    @property
    def shrunk(self) -> bool:
        """True when this plan was elastically narrowed below the operator's
        requested topology (always False for fixed-world plans)."""
        return (
            self.requested_axes is not None
            and self.requested_axes != self.describe()
        )

    # -- topology ------------------------------------------------------------

    def axis_size(self, name: str) -> int:
        """Size of a mesh axis; 1 when the axis is absent (the identity
        for every layout derivation — an absent axis shards nothing)."""
        return int(self.mesh.shape.get(name, 1))

    @property
    def data_size(self) -> int:
        return self.axis_size(DATA_AXIS)

    @property
    def seq_size(self) -> int:
        return self.axis_size(SEQ_AXIS)

    @property
    def model_size(self) -> int:
        return self.axis_size(MODEL_AXIS)

    @property
    def pipe_size(self) -> int:
        return self.axis_size(PIPE_AXIS)

    @property
    def single_device(self) -> bool:
        return is_single_device(self.mesh)

    @property
    def data_only(self) -> bool:
        """Several chips, and ``data`` the only axis wider than 1: plain
        data parallelism, what the trainer's data island needs."""
        return 1 < self.data_size == int(self.mesh.devices.size)

    def describe(self) -> Dict[str, int]:
        """``{axis: size}`` in mesh order — the spelling manifests, the
        pre-flight report and bench JSON all record."""
        return {
            str(name): int(size)
            for name, size in zip(self.mesh.axis_names, self.mesh.devices.shape)
        }

    def stage_map(self, num_layers: int) -> Dict[str, str]:
        """``{"stage_k": "layer_lo..layer_hi"}`` — which contiguous encoder
        layers each pipe rank owns (pre-flight report / bench JSON). Empty
        when the plan has no multi-way pipe axis."""
        if self.pipe_size <= 1:
            return {}
        from .pipeline import stage_assignment

        return {
            f"stage_{k}": f"layer_{lo}..layer_{hi - 1}"
            for k, (lo, hi) in stage_assignment(
                int(num_layers), self.pipe_size
            ).items()
        }

    def stage_specs(self, params):
        """Stage-local param PartitionSpec tree (trunk leaves over
        ``pipe``, TP dims honored) — see ``pipeline.stage_param_specs``."""
        from .pipeline import stage_param_specs

        return stage_param_specs(params, self)

    # -- derived shardings ---------------------------------------------------

    def named(self, spec: P) -> NamedSharding:
        """A NamedSharding over this plan's mesh. The one constructor
        call sites outside ``parallel/`` go through (MLA009)."""
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return self.named(P())

    def put_replicated(self, tree):
        """Place a host tree fully replicated over the mesh."""
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self.replicated()), tree
        )

    def batch_spec(self, *, shard_seq: bool = False, ndim: int = 2) -> P:
        return batch_pspec(self.mesh, shard_seq=shard_seq, ndim=ndim)

    def batch_shardings(self, batch_tree, *, shard_seq: bool = False):
        return batch_sharding(self.mesh, batch_tree, shard_seq=shard_seq)

    def data_island(self, body, *, row_args: Sequence[bool]):
        """``body`` as ONE ``shard_map`` over the mesh, run once a chip
        (``data_only`` meshes): an argument flagged in ``row_args`` enters
        with its micro-batch-major rows (``[G, B, ...]``, axis 1, the
        layout ``make_global_array(batch_axis=1)`` places) split over
        ``data``, the others replicated. Every output leaves UNREDUCED,
        the chips' values stacked on a new leading axis sharded over
        ``data`` (``[data, ...]``): the caller sums over it under GSPMD,
        which then chooses the collective (an all-reduce, or a
        reduce-scatter where the consumer is sharded)."""
        from .compat import shard_map

        def stacked(*args):
            return jax.tree_util.tree_map(lambda x: x[None], body(*args))

        rows = P(None, DATA_AXIS)
        return shard_map(
            stacked, mesh=self.mesh,
            in_specs=tuple(rows if flag else P() for flag in row_args),
            out_specs=P(DATA_AXIS), check_vma=False,
        )

    def param_specs(self, params):
        return param_pspecs(params, self.mesh)

    def zero1(self, tree, *, min_size: int = 16384,
              stage_pipe: bool = False):
        """The padding-aware per-leaf ZeRO-1 placement plan (over the
        ``data`` axis; TP axes honored; with ``stage_pipe`` the ``pipe``
        axis claims its stage-scope dim first, so the data-axis plan runs
        within each stage's leaf set) — see ``sharding.zero1_plan``."""
        return zero1_plan(tree, self.mesh, min_size=min_size,
                          stage_pipe=stage_pipe)

    def zero1_param_shardings(self, zplan):
        """NamedSharding tree for a ZeRO-1 leaf-plan tree (the layout the
        padded grads/params are constrained onto inside the train step)."""
        return jax.tree_util.tree_map(
            lambda z: self.named(z.spec), zplan,
            is_leaf=lambda x: isinstance(x, ZeroLeafPlan),
        )

    def opt_state_shardings(self, state_shapes, *,
                            zero1: bool, min_size: int = 16384,
                            stage_pipe: bool = False):
        """NamedSharding tree for an optimizer-state (shape) tree:
        ZeRO-1 layout when ``zero1`` (each shardable leaf over ``data``),
        otherwise the replicated-with-TP-rules layout; ``stage_pipe``
        additionally lands each stage-scope leaf's moments on the
        ``pipe`` axis (stage-local optimizer state — independent of the
        min_size gate, which only governs the data axis). ONE derivation
        for the trainer's ``init_opt_state``, the checkpoint
        reconciliation and the layout-consistency tests."""
        return jax.tree_util.tree_map(
            lambda spec: self.named(spec),
            zero_pspecs(
                state_shapes, self.mesh,
                # min_size=inf disables the data axis: TP rules still
                # apply, everything else replicates (the non-ZeRO layout)
                min_size=min_size if zero1 else math.inf,
                stage_pipe=stage_pipe,
            ),
        )

