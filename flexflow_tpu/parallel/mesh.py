"""Device-mesh abstraction: SOAP partition configs → JAX shardings.

This is the TPU-native replacement for the reference's mapper + Legion
partition machinery (reference: src/mapper/mapper.cc:33-146,
src/runtime/model.cc:466-606).  The reference creates a Legion index task
space per op shaped like the op's ``ParallelConfig`` and maps each point
task to the GPU in ``device_ids``; Legion inserts the data movement when
consecutive ops use different partitions.

On TPU, the same SOAP space is expressed through one global
``jax.sharding.Mesh`` whose axes are the *prime factors* of the device
count.  Any per-dim partition degree that divides the device count then
lowers to a ``PartitionSpec`` assigning a subset of mesh axes to that
tensor dim; XLA GSPMD inserts the resharding collectives (over ICI) when
producer and consumer specs differ — the analogue of Legion's implicit
region copies.

Example: 8 devices → mesh axes ('m0','m1','m2'), each size 2.  A Conv2D
config with dims (4, 1, 2, 1) [N,H,W,C] lowers to
PartitionSpec(('m0','m1'), None, ('m2',), None); a following Dense with
dims (8, 1) lowers to PartitionSpec(('m0','m1','m2'), None) — GSPMD emits
the all-to-all between them.

The walk from degrees to axis groups (``assign_axes``) lives here once.
An op's output passes its dims' SOAP *roles* (``dim_roles``), and
t5x-style logical-axis rules say which class of mesh axis a role may
take: on a hybrid ICI×DCN mesh (``parallel/distributed.hybrid_machine``,
axes ``("dcn", "m0", ...)``) only the sample dim may span hosts, so the
gradient all-reduce stays the only collective over DCN, which is what
``simulator/machine.TPUMachineModel.dcn_spill_time`` prices.  A caller
that passes no roles (weights, batches, pipeline buffers) gets the plain
greedy walk over every axis.  Without a ``dcn`` axis the two are the same
walk.  docs/lowering.md has the rules and the spill.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import ParallelConfig

# -- roles and rules ---------------------------------------------------

SAMPLE = "sample"        # the batch dim (dim 0; Sample in SOAP)
PARAMETER = "parameter"  # a dim whose partitioning splits a weight
ATTRIBUTE = "attribute"  # any other tensor dim

DCN_AXIS = "dcn"

# role -> the axis classes it may take, in preference order, after t5x's
# logical-axis rules.  Axis classes: "ici" = every non-dcn mesh axis,
# "dcn" = the cross-host axis.  A role whose preference omits "dcn" may
# still spill onto it as a legality fallback — the spill is *recorded*
# (Machine.plan, doctor's WARN, the simulator's surcharge) rather than
# forbidden, because a degree the mesh cannot express intra-host must
# still lower.
AXIS_RULES: Dict[str, Tuple[str, ...]] = {
    SAMPLE: (DCN_AXIS, "ici"),     # batch may span hosts: grad all-reduce
    PARAMETER: ("ici",),           # weight shards stay intra-host
    ATTRIBUTE: ("ici",),           # activation splits stay intra-host
}
_ANY_AXIS = (DCN_AXIS, "ici")


def _prime_factors(n: int) -> List[int]:
    out: List[int] = []
    d = 2
    while d * d <= n:
        while n % d == 0:
            out.append(d)
            n //= d
        d += 1
    if n > 1:
        out.append(n)
    return sorted(out, reverse=True)


def hybrid_axis_layout(num_devices: int, num_hosts: int = 1
                       ) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """(axis_names, axis_sizes) of the mesh over this many devices: the
    prime factors, larger first, as ``m0, m1, ...``; with ``num_hosts``
    > 1 dividing the count, a leading ``dcn`` axis of that size over the
    factors of one host's share.  ``Machine`` and ``hybrid_machine``
    build their meshes from it, and the simulator asks it "where would
    this degree land?" without constructing devices."""
    n = int(num_devices)
    h = int(num_hosts)
    if h <= 1 or n % h != 0:
        factors = _prime_factors(n) if n > 1 else [1]
        return (tuple(f"m{i}" for i in range(len(factors))), tuple(factors))
    per = n // h
    ici = tuple(_prime_factors(per)) if per > 1 else (1,)
    return ((DCN_AXIS,) + tuple(f"m{i}" for i in range(len(ici))),
            (h,) + ici)


def dim_roles(op, rank: int) -> Tuple[str, ...]:
    """Per-tensor-dim SOAP role for an op's output: dim 0 is ``sample``;
    a dim that any weight's ``partition_dims`` shards with is
    ``parameter``; the rest are ``attribute``.  ``op`` may be None (a
    bare degree vector: sample, then attributes)."""
    roles = [ATTRIBUTE] * rank
    if rank > 0:
        roles[0] = SAMPLE
    w_op = getattr(op, "share_from", None) or op
    for w in getattr(w_op, "weights", ()):
        for pd in (w.partition_dims or ()):
            if pd is not None and 0 < pd < rank:
                roles[pd] = PARAMETER
    return tuple(roles)


def assign_axes(axis_names: Sequence[str], axis_sizes: Sequence[int],
                degrees: Sequence[int],
                roles: Optional[Sequence[str]] = None,
                ) -> Tuple[List[Tuple[str, ...]], Tuple[Tuple[int, int], ...]]:
    """Assign disjoint mesh-axis groups whose sizes multiply to each
    requested degree, greedily over the axes in mesh order.

    With ``roles``, sample dims claim axes first (so the batch takes
    ``dcn`` + the widest ICI axes, matching the hybrid mesh's
    leading-batch-axis design); the remaining dims walk in index order,
    preferring the axis classes their role's rule names and spilling onto
    the rest only when the degree is otherwise inexpressible.  Without
    them every dim walks in index order and may take any axis.  Returns
    ``(groups, spill)`` where ``spill`` lists ``(dim, dcn_share)`` for
    every dim that had to take the ``dcn`` axis against its rule
    (dcn_share = the part of its degree crossing hosts).

    Raises ValueError when a degree cannot be composed from the remaining
    axes (e.g. degree 3 on an 8-device mesh).
    """
    n = len(degrees)
    if roles is None:
        order = list(range(n))
        prefs = [_ANY_AXIS] * n
    else:
        order = ([i for i, r in enumerate(roles) if r == SAMPLE]
                 + [i for i, r in enumerate(roles) if r != SAMPLE])
        prefs = [AXIS_RULES[r] for r in roles]
    remaining: List[Tuple[Optional[str], int]] = list(
        zip(axis_names, axis_sizes))
    groups: List[Tuple[str, ...]] = [()] * n
    spill: List[Tuple[int, int]] = []
    for i in order:
        need = int(degrees[i])
        pref = prefs[i]
        group: List[str] = []
        dcn_share = 1
        # pass 1: only axis classes the rule names; pass 2: everything
        # (legality fallback — records a spill for dcn takes).
        for allowed in (pref, _ANY_AXIS):
            for j in range(len(remaining)):
                name, size = remaining[j]
                if name is None:
                    continue
                cls = DCN_AXIS if name == DCN_AXIS else "ici"
                if cls not in allowed:
                    continue
                if need % size == 0:
                    group.append(name)
                    need //= size
                    remaining[j] = (None, 0)
                    if cls == DCN_AXIS and DCN_AXIS not in pref:
                        dcn_share *= size
                    if need == 1:
                        break
            if need == 1:
                break
        if need != 1:
            raise ValueError(
                f"partition degree {degrees[i]} not expressible over mesh "
                f"axes {dict(zip(axis_names, axis_sizes))} "
                f"(degrees={list(degrees)})")
        if dcn_share > 1:
            spill.append((i, dcn_share))
        groups[i] = tuple(group)
    return groups, tuple(sorted(spill))


def spec_entries(groups: Sequence[Tuple[str, ...]]) -> List:
    """Axis groups → PartitionSpec entries: a scalar for a singleton
    group, None for an unsharded dim, trailing Nones trimmed."""
    entries = [g if len(g) > 1 else (g[0] if g else None) for g in groups]
    while entries and entries[-1] is None:
        entries.pop()
    return entries


def spec_string(groups: Sequence[Tuple[str, ...]]) -> str:
    """Human/sidecar rendering of a spec, e.g.
    ``"('dcn','m0'), None, 'm1'"`` — stable across jax versions (no
    PartitionSpec repr dependency)."""
    parts = []
    for e in spec_entries(groups):
        if e is None:
            parts.append("None")
        elif isinstance(e, tuple):
            parts.append("(" + ",".join(f"'{a}'" for a in e) + ")")
        else:
            parts.append(f"'{e}'")
    return ", ".join(parts) if parts else "replicated"


def _fit(values: Sequence, rank: Optional[int], fill) -> list:
    """Pad with ``fill`` or truncate to an array's actual rank (e.g. a
    (B,1) label tensor under a 2-D config)."""
    values = list(values)
    if rank is None:
        return values
    return (values + [fill] * (rank - len(values)))[:rank]


class Machine:
    """The machine model: an N-device mesh with prime-factored axes.

    ``devices`` defaults to ``jax.devices()``.  For multi-host runs the
    caller passes the global device list (after ``jax.distributed``
    initialization); axis order puts larger factors first so that batch-dim
    sharding lands on the widest axis groups.
    """

    def __init__(self, devices: Optional[Sequence] = None, num_devices: Optional[int] = None,
                 mesh: Optional[Mesh] = None):
        if mesh is not None:
            # Adopt a prebuilt mesh (e.g. a hybrid ICI×DCN mesh from
            # parallel/distributed.py); axis order is the mesh's order.
            self.mesh = mesh
            self.devices = list(mesh.devices.flat)
            self.axis_names = tuple(mesh.axis_names)
            self.axis_sizes = tuple(mesh.devices.shape)
            return
        if devices is None:
            devices = jax.devices()
            if num_devices is not None:
                devices = devices[:num_devices]
        self.devices = list(devices)
        self.axis_names, self.axis_sizes = hybrid_axis_layout(
            len(self.devices))
        dev_array = np.array(self.devices).reshape(self.axis_sizes)
        self.mesh = Mesh(dev_array, self.axis_names)

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    # -- spec lowering -----------------------------------------------------
    def axes_for_degrees(self, degrees: Sequence[int],
                         roles: Optional[Sequence[str]] = None
                         ) -> List[Tuple[str, ...]]:
        """Disjoint mesh-axis groups whose sizes multiply to each requested
        degree (``assign_axes`` over this mesh); raises if a degree cannot
        be composed from the remaining axes."""
        return assign_axes(self.axis_names, self.axis_sizes, degrees,
                           roles)[0]

    def spec_for_config(self, pc: ParallelConfig, rank: Optional[int] = None,
                        roles: Optional[Sequence[str]] = None) -> PartitionSpec:
        """Lower a ParallelConfig to a PartitionSpec over this mesh.

        ``pc.dims[i]`` is the partition degree of tensor dim i (natural
        order, batch first).  ``rank`` pads/truncates to the actual array
        rank; ``roles`` (``dim_roles`` of the op) are fitted with it."""
        if roles is not None:
            roles = _fit(roles, rank if rank is not None else len(pc.dims),
                         ATTRIBUTE)
        groups = self.axes_for_degrees(_fit(pc.dims, rank, 1), roles)
        return PartitionSpec(*spec_entries(groups))

    def sharding_for_config(self, pc: ParallelConfig, rank: Optional[int] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for_config(pc, rank))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def batch_sharding(self, degree: int) -> NamedSharding:
        """Sharding for a host-fed batch array: first dim split ``degree``
        ways, everything else replicated."""
        if degree <= 1:
            return self.replicated()
        axes = self.axes_for_degrees([degree])[0]
        return NamedSharding(self.mesh,
                             PartitionSpec(axes if len(axes) > 1 else axes[0]))

    def sharding_for_spec(self, spec: PartitionSpec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constraint(self, x, pc: ParallelConfig,
                   roles: Optional[Sequence[str]] = None):
        """Apply a sharding constraint for an op output inside jit — the
        analogue of the op's Legion output partition."""
        spec = self.spec_for_config(pc, rank=x.ndim, roles=roles)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    # -- introspection -----------------------------------------------------
    def plan(self, ops) -> Dict[str, Dict[str, object]]:
        """Where each compiled op's output lands on this mesh, for the
        provenance sidecar and doctor: ``{op: {spec, roles, dcn_spill}}``;
        ``dcn_spill`` (``[[dim, dcn_share], ...]``) only where a non-sample
        dim had to take the ``dcn`` axis — the thing the search's DCN
        surcharge exists to prevent."""
        out: Dict[str, Dict[str, object]] = {}
        for op in ops:
            if getattr(op, "pc", None) is None:
                continue
            rank = op.output.num_dims
            roles = dim_roles(op, rank)
            groups, spill = assign_axes(
                self.axis_names, self.axis_sizes,
                _fit(op.constraint_pc().dims, rank, 1), roles)
            row: Dict[str, object] = {"spec": spec_string(groups),
                                      "roles": "".join(r[0] for r in roles)}
            if spill:
                row["dcn_spill"] = [list(s) for s in spill]
            out[op.name] = row
        return out

    def __repr__(self):
        return f"Machine({dict(zip(self.axis_names, self.axis_sizes))})"
