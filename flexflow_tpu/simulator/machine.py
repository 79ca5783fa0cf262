"""TPU machine model for the execution simulator.

TPU-native analogue of the reference device/bandwidth graph
(reference: src/runtime/simulator.cu:21-74 — per-GPU compute devices plus
COMM devices with three hardcoded bandwidths: intra-node ~20 GB/s,
inter-node 12/numNodes, gpu↔dram 16).

The TPU model replaces those constants with a 2-D ICI torus: each chip has
a (x, y) coordinate; transfer cost between chips scales with Manhattan
hop distance on the torus (wraparound links), using per-link ICI bandwidth.
Multi-host slices add a DCN tier: chips on different hosts pay the DCN
bandwidth instead.  Numbers default to TPU v5e
(peak 197 TFLOP/s bf16, HBM 819 GB/s, ICI ~45 GB/s/link/direction,
DCN ~25 GB/s/host) and are all overridable.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, Tuple

# Roofline constants fitted to real-chip measurements by tools/calibrate.py.
CALIBRATION_PATH = os.path.join(os.path.dirname(__file__), "machine_v5e.json")

# Published peaks of ONE chip, keyed by ``device_kind`` as JAX reports
# it.  The only place a peak is written down: utilization figures
# (observability/stepstats.py) divide by these, and the
# simulator's defaults below are the v5e row.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bandwidth": 819e9,
                    "hbm_capacity": 16e9},
}


def device_peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind``.  An unknown kind
    is an error, never a default: a utilization divided by another
    chip's peak is a wrong number with nothing to flag it."""
    try:
        return DEVICE_PEAKS[device_kind]["bf16_flops"]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"sourced row to DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})") from None

_V5E = DEVICE_PEAKS["TPU v5 lite"]


@dataclasses.dataclass
class TPUMachineModel:
    num_devices: int = 8
    chips_per_host: int = 8
    peak_flops: float = _V5E["bf16_flops"]        # bf16 MXU
    hbm_bandwidth: float = _V5E["hbm_bandwidth"]  # bytes/s
    ici_bandwidth: float = 45e9       # bytes/s per link per direction
    dcn_bandwidth: float = 25e9       # bytes/s per host
    kernel_launch_overhead: float = 2e-6   # s; XLA per-fused-region overhead
    mxu_efficiency: float = 0.45      # achievable fraction of peak for convs/matmuls
    backward_multiplier: float = 2.0  # bwd ≈ dgrad + wgrad vs one fwd
    # float32 element operations a second on the VPU and EUP, for work
    # that runs there and not on the MXU (Mamba's scan: ops/ssm.py): the
    # scan's forward kernel, 7 a token, channel and state, measured on a
    # v5e at 8192 x 5120 x 16 (4.7 G in 1.38 ms; PERF.md section 6)
    vpu_ops_per_s: float = 3.4e12
    # Host tier (row-sparse host-resident embeddings, reference hetero
    # ZCM placement): chip<->host PCIe and host DDR stream bandwidth.
    pcie_bandwidth: float = 32e9      # bytes/s per direction (gen4 x16)
    host_memory_bandwidth: float = 100e9  # bytes/s effective DDR gather
    # Fixed per-transfer host<->device latency — tools/calibrate.py fits
    # it from the measured host_xfer ladder alongside pcie_bandwidth.
    host_xfer_latency: float = 0.0
    hbm_capacity: float = _V5E["hbm_capacity"]  # bytes per chip
    # Per-op-family roofline overrides fitted by tools/calibrate.py once
    # enough measured families land (e.g. {"Conv2D": 0.5, "LSTM": 0.3});
    # families absent here use the global constants above.  One global
    # MXU efficiency cannot describe conv im2col, LSTM scan steps, and
    # gather-bound embeddings at once — the per-family fit is what makes
    # the simulated-vs-measured agreement bound tight.
    op_efficiency: Dict[str, float] = dataclasses.field(default_factory=dict)
    op_backward_multiplier: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @classmethod
    def calibrated(cls, **kw) -> "TPUMachineModel":
        """Machine model with roofline constants loaded from the committed
        on-chip calibration fit (machine_v5e.json) when present — the
        analogue of the reference replacing its three hardcoded bandwidth
        constants with per-machine measurements.  Explicit kwargs win."""
        if os.path.exists(CALIBRATION_PATH):
            try:
                with open(CALIBRATION_PATH) as f:
                    overrides = json.load(f)
            except Exception:
                overrides = {}
            names = {f.name for f in dataclasses.fields(cls)}
            for k, v in overrides.items():
                if k in names and k not in kw:
                    kw[k] = v
        return cls(**kw)

    def __post_init__(self):
        # near-square 2-D torus layout, the v5e topology family
        # (e.g. 16 chips → 4x4, 8 → 4x2)
        n = self.num_devices
        x = int(math.sqrt(n))
        while x > 1 and n % x != 0:
            x -= 1
        self.torus = (max(1, x), n // max(1, x))
        # degree-vector -> dcn_spill result; the search's delta loop
        # re-asks for thousands of candidate configs
        self._spill_cache: Dict[Tuple[int, ...], Tuple[Tuple[int, int], ...]] = {}

    def coord(self, dev: int) -> Tuple[int, int]:
        return (dev % self.torus[0], dev // self.torus[0])

    def hops(self, a: int, b: int) -> int:
        """Manhattan distance on the wraparound torus."""
        if a == b:
            return 0
        (ax, ay), (bx, by) = self.coord(a), self.coord(b)
        dx = abs(ax - bx)
        dy = abs(ay - by)
        dx = min(dx, self.torus[0] - dx)
        dy = min(dy, self.torus[1] - dy)
        return dx + dy

    def same_host(self, a: int, b: int) -> bool:
        return a // self.chips_per_host == b // self.chips_per_host

    def transfer_time(self, a: int, b: int, num_bytes: float) -> float:
        """Point-to-point transfer cost in seconds."""
        if a == b or num_bytes <= 0:
            return 0.0
        if self.same_host(a, b):
            return num_bytes * max(1, self.hops(a, b)) / self.ici_bandwidth
        return num_bytes / self.dcn_bandwidth

    def allreduce_time(self, devices, num_bytes: float) -> float:
        """Ring allreduce over ICI: 2·(n-1)/n · bytes / link_bw (the cost
        of the psum XLA emits for gradient sync — replaces the reference's
        replica-gather model, optimizer_kernel.cu:168-180)."""
        n = len(set(devices))
        if n <= 1 or num_bytes <= 0:
            return 0.0
        bw = self.ici_bandwidth
        if not all(self.same_host(devices[0], d) for d in devices):
            bw = self.dcn_bandwidth
        return 2.0 * (n - 1) / n * num_bytes / bw

    # -- hierarchical-mesh placement ----------------------------------------
    @property
    def num_hosts(self) -> int:
        return max(1, -(-self.num_devices // self.chips_per_host))

    def dcn_spill(self, degrees) -> Tuple[Tuple[int, int], ...]:
        """Non-sample dims of a partition-degree vector that the executor
        (``parallel/mesh.assign_axes``, with an op output's roles) would
        have to place on the ``dcn`` axis of this machine's hybrid mesh —
        ``((dim, dcn_share), ...)``, empty on a single-host machine or
        when every non-sample degree fits the ICI axes."""
        if self.num_hosts <= 1 or self.num_devices % self.chips_per_host:
            return ()
        key = tuple(degrees)
        hit = self._spill_cache.get(key)
        if hit is not None:
            return hit
        from ..parallel.mesh import (assign_axes, dim_roles,
                                     hybrid_axis_layout)

        names, sizes = hybrid_axis_layout(self.num_devices, self.num_hosts)
        try:
            _, spill = assign_axes(names, sizes, key,
                                   dim_roles(None, len(key)))
        except ValueError:
            # inexpressible degrees never reach execution (legalize_pc
            # clamps first) — charge nothing rather than guess
            spill = ()
        self._spill_cache[key] = spill
        return spill

    def dcn_spill_time(self, degrees, part_bytes: float) -> float:
        """Seconds of DCN traffic a strategy pays per step because a
        non-sample dim crossed hosts: each spilled dim reshards the
        part's bytes over the ``dcn`` axis (ring factor), instead of the
        gradient all-reduce being the only DCN-crossing collective.
        This is the search pressure that keeps searched strategies
        pod-shaped."""
        t = 0.0
        for _dim, share in self.dcn_spill(degrees):
            t += 2.0 * (share - 1) / share * part_bytes / self.dcn_bandwidth
        return t
