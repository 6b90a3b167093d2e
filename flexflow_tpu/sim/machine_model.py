"""Machine models: TPU chip + interconnect analytic cost.

TPU-native re-design of the reference's machine-model hierarchy
(reference: simulator.h:212-606 — SimpleMachineModel /
EnhancedMachineModel / NetworkedMachineModel; src/runtime/machine_model.cc;
network topology + routing in src/runtime/network.cc). Where the reference
models PCIe/NVLink/NIC segments and simulates NCCL rings, the TPU model is
built around the hardware that actually exists here:

* a **chip spec** (MXU peak FLOP/s, HBM bandwidth/capacity, vector-unit
  throughput) — plays the role of the reference's per-GPU microbenchmarks;
* an **ICI torus** within a slice (per-link bandwidth + per-hop latency,
  bidirectional links, 2D/3D wrap-around) — plays NVLink/GPUDirect;
* **DCN** across slices (per-host bandwidth, much higher latency) — plays
  the inter-node NIC model.

Collective costs use the standard ring/torus lower-bound formulas (the same
algebra the scaling literature uses): an all-reduce of S bytes over an axis
of n chips moves ``2*(n-1)/n * S`` bytes through each link, etc. These are
the costs XLA's collectives approach on ICI, which is what makes an
analytic model viable where the reference needed event-level NCCL
simulation.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class TPUChipSpec:
    """Peak numbers for one TPU chip (public figures; the profiling cost
    model recalibrates against measured kernels — reference analog:
    Op::inner_measure_operator_cost's cudaEvent timing, model.cu:17-53)."""

    name: str
    peak_bf16_flops: float          # FLOP/s on the MXU, bf16 inputs
    hbm_bandwidth: float            # bytes/s
    hbm_capacity: float             # bytes
    ici_link_bandwidth: float       # bytes/s per link per direction
    ici_num_links: int              # links per chip (torus degree)
    ici_latency: float = 1e-6      # per-hop seconds
    dcn_bandwidth: float = 25e9     # bytes/s per host across slices
    dcn_latency: float = 10e-6
    # achievable fractions of peak (roofline knee calibration)
    mxu_efficiency: float = 0.55
    hbm_efficiency: float = 0.8
    kernel_overhead: float = 2e-6   # fixed per-fused-region launch cost
    # fixed per-STEP dispatch/launch overhead (host->device program
    # launch). Fitted by sim/calibrate.py; see CALIBRATION.md.
    step_overhead: float = 0.0


CHIP_PRESETS: Dict[str, TPUChipSpec] = {
    # Figures from public spec sheets / the scaling-book tables (approximate).
    "v4": TPUChipSpec("v4", 275e12, 1.23e12, 32 << 30, 45e9, 6),
    # v5e efficiencies fitted on 2026-07-29 to two float32 train-step
    # times of the bench transformer (b8 L4 s256 h512 and b8 L12 s512
    # h1024) taken on a v5e reached over a network link, by the two-point
    # fit of sim/calibrate.py: real = 1.35 * simulated + overhead, the
    # 1.35 folded into both efficiencies (0.55/1.35, 0.8/1.35). The
    # fitted per-step overhead was that link's and is not carried here.
    # bf16 was never fitted, and nothing has been measured on an attached
    # chip: ROADMAP S2 recalibrates.
    "v5e": TPUChipSpec("v5e", 197e12, 0.82e12, 16 << 30, 45e9, 4,
                       mxu_efficiency=0.41, hbm_efficiency=0.59),
    "v5p": TPUChipSpec("v5p", 459e12, 2.77e12, 95 << 30, 90e9, 6),
    "v6e": TPUChipSpec("v6e", 918e12, 1.64e12, 32 << 30, 90e9, 4),
    # hermetic-test chip: round numbers so expected costs are exact
    # (SURVEY.md §4: the reference has no deterministic machine-model tests;
    # we add them)
    "test": TPUChipSpec(
        "test", 1e12, 1e11, 8 << 30, 1e10, 4,
        ici_latency=1e-6, dcn_bandwidth=1e9, dcn_latency=1e-5,
        mxu_efficiency=1.0, hbm_efficiency=1.0, kernel_overhead=0.0,
    ),
    # host CPU running a VIRTUAL device mesh
    # (xla_force_host_platform_device_count): all "devices" share one
    # socket, so sharding buys no compute and collectives are memcpys.
    # Modeled so the search tells the truth on this platform: it should
    # conclude that parallelism does not pay and keep the graph simple
    # (used with shared_host=True, which removes the per-device compute
    # credit entirely).
    "cpu-host": TPUChipSpec(
        "cpu-host", 2e11, 2e10, 16 << 30, 5e9, 1,
        ici_latency=5e-6, dcn_bandwidth=1e9, dcn_latency=5e-5,
        mxu_efficiency=0.5, hbm_efficiency=0.5, kernel_overhead=5e-6,
        # per-PROGRAM overhead on the shared host. A no-op jitted
        # dispatch is ~0.2 ms, but a real stage executable pays thread-
        # pool fork/join + buffer setup per launch: the AE playoff
        # measured the host-driven GPipe engine (2·M·P launches/step)
        # ~100 ms slower than the single fused program on dlrm —
        # ~6 ms per launch over 16 launches. Charged once per fused
        # step (cancels when comparing single-program plans) and
        # 2·M·P times for pipe plans (unity._pipe_adjusted), which is
        # what makes host-driven pipelining honestly unattractive here.
        step_overhead=5e-3,
    ),
}


class MachineModel:
    """Interface: collective + point-to-point costs over a named mesh.

    reference: MachineModel base (simulator.h:212-…) exposing
    get_*_bandwidth / latency used by simulate_runtime's comm-task sizing.
    Axis degrees come from the mesh the strategy targets; the model decides
    what fabric each axis rides (ICI vs DCN).
    """

    chip: TPUChipSpec

    def num_devices(self) -> int:
        raise NotImplementedError

    def effective_parallelism(self, parts: int) -> float:
        """Wall-clock compute speedup from splitting work ``parts`` ways.
        Real chips: ``parts`` (each shard runs on its own MXU). A virtual
        shared-host mesh: 1.0 — the shards time-slice one socket, so
        sharding buys nothing (the cost model consults this so the search
        doesn't hallucinate speedups the platform can't deliver)."""
        return float(max(parts, 1))

    def sharded_compute_penalty(self, non_data_axes) -> float:
        """Compute multiplier for ops sharded beyond the batch dim (see
        SimpleMachineModel: >1 only on shared-host virtual meshes)."""
        return 1.0

    def serialization_factor(self) -> float:
        """How many device-programs' work funnels through one execution
        resource. Real chips: 1 (each device runs its own program in
        parallel — per-device cost IS wall-clock). Shared-host virtual
        meshes: the device count — every program time-slices one socket,
        so an op REPLICATED across an idle mesh axis is honestly charged
        for each redundant replica."""
        return 1.0

    def sharded_tiny_op_latency(self) -> float:
        """Fixed per-direction cost for a small sharded op (>0 only on
        shared-host virtual meshes; see SimpleMachineModel)."""
        return 0.0

    def gather_inefficiency(self) -> float:
        """Embedding gather/scatter multiplier (>1 only on shared-host
        virtual meshes; real chips gather at memory speed)."""
        return 1.0

    def combine_sync_axes(self) -> bool:
        """Whether grad-sync for a weight replicated over several mesh
        axes is priced as ONE allreduce over the combined degree (true on
        shared hosts, where any axis decomposition funnels through the
        same memory system) or per-axis (real machines, where each axis
        rides its own fabric — DCN vs ICI — and must be priced there)."""
        return False

    # every cost takes per-participant payload bytes and the axis degree
    def allreduce_time(self, bytes_per_device: float, degree: int, axis: str = "") -> float:
        raise NotImplementedError

    def allgather_time(self, bytes_per_device: float, degree: int, axis: str = "") -> float:
        raise NotImplementedError

    def reducescatter_time(self, bytes_per_device: float, degree: int, axis: str = "") -> float:
        raise NotImplementedError

    def alltoall_time(self, bytes_per_device: float, degree: int, axis: str = "") -> float:
        raise NotImplementedError

    def permute_time(self, bytes_per_device: float, degree: int, axis: str = "") -> float:
        raise NotImplementedError


class SimpleMachineModel(MachineModel):
    """v0 model: every mesh axis rides ICI with the same per-link bandwidth
    (reference analog: SimpleMachineModel's flat intra-node bandwidth,
    simulator.h:212-260). Good default for a single slice where the mesh is
    laid out on the torus by the XLA runtime.
    """

    def __init__(self, chip: TPUChipSpec = CHIP_PRESETS["v5e"],
                 n_devices: int = 1, shared_host: bool = False):
        self.chip = chip
        self._n = n_devices
        self.shared_host = shared_host

    def num_devices(self) -> int:
        return self._n

    def effective_parallelism(self, parts: int) -> float:
        if self.shared_host:
            return 1.0
        return float(max(parts, 1))

    def sharded_compute_penalty(self, non_data_axes) -> float:
        """Shared-host compute multiplier for ops sharded beyond the
        batch dim. Fitted against the AE playoff's measured step times
        (scripts/fit_shared_host.py): XLA's per-shard programs for
        model/seq-sharded ops ran ~1.6x their batch-sharded cost on the
        one-core virtual mesh (masking + per-shard collectives the
        roofline doesn't see), and the expert-parallel dispatch family
        (capacity gathers/scatters per shard) another ~4.5x on top.
        Real chips: 1.0 — each device genuinely owns its shard."""
        if not self.shared_host or not non_data_axes:
            return 1.0
        penalty = 1.6
        if "expert" in non_data_axes:
            penalty *= 4.5
        return penalty

    def serialization_factor(self) -> float:
        return float(self._n) if self.shared_host else 1.0

    def sharded_tiny_op_latency(self) -> float:
        """Fixed per-direction cost for a SMALL sharded op on the shared
        host (fitted: the n-branch MoE's per-expert GEMMs are overhead-
        dominated — per-shard program setup swamps their ~0.1 MFLOP of
        compute, which the roofline prices at microseconds)."""
        return 5e-4 if self.shared_host else 0.0

    def gather_inefficiency(self) -> float:
        """Embedding gather/scatter multiplier on the shared host: XLA
        CPU executes row gathers as scalar loops, measured ~3x the
        roofline's streaming estimate (dlrm/xdl DP legs). Real chips
        gather at memory speed: 1.0."""
        return 3.0 if self.shared_host else 1.0

    def combine_sync_axes(self) -> bool:
        return self.shared_host

    # ring formulas; ICI links are bidirectional so a ring all-gather can use
    # both directions → effective per-link bandwidth ×2.
    def _serial(self, degree: int) -> float:
        """Shared-host serialization: the ring formulas assume ``degree``
        links transferring concurrently; a virtual CPU mesh funnels every
        'link' through ONE memory system, so collective wall-clock scales
        back up by the degree. Without this the search under-prices
        collectives ~n× on the virtual mesh and picks sharded strategies
        that lose in real wall-clock (observed on the AE protocol)."""
        return float(degree) if self.shared_host else 1.0

    def _bw(self, axis: str) -> float:
        return self.chip.ici_link_bandwidth * 2.0

    def _bw_unidir(self, axis: str) -> float:
        """One-direction bandwidth (a permute shifts data one way only)."""
        return self._bw(axis) / 2.0

    def _lat(self, axis: str) -> float:
        return self.chip.ici_latency

    def allgather_time(self, bytes_per_device, degree, axis=""):
        if degree <= 1:
            return 0.0
        return self._serial(degree) * (degree - 1) * (
            bytes_per_device / self._bw(axis) + self._lat(axis))

    def reducescatter_time(self, bytes_per_device, degree, axis=""):
        # same volume pattern as all-gather (each device ends with 1/degree)
        if degree <= 1:
            return 0.0
        shard = bytes_per_device / degree
        return self._serial(degree) * (degree - 1) * (
            shard / self._bw(axis) + self._lat(axis))

    def allreduce_time(self, bytes_per_device, degree, axis=""):
        # reduce-scatter + all-gather of the scattered shard
        if degree <= 1:
            return 0.0
        shard = bytes_per_device / degree
        return self._serial(degree) * 2 * (degree - 1) * (
            shard / self._bw(axis) + self._lat(axis))

    def alltoall_time(self, bytes_per_device, degree, axis=""):
        if degree <= 1:
            return 0.0
        # each device exchanges (degree-1)/degree of its payload; on a
        # bidirectional ring average hop distance degree/4 over degree
        # concurrent links → effective time ≈ vol / (2·bw)
        vol = bytes_per_device * (degree - 1) / degree
        return (self._serial(degree) * vol / (2.0 * self._bw(axis))
                + self._lat(axis) * degree / 2)

    def permute_time(self, bytes_per_device, degree, axis=""):
        if degree <= 1:
            return 0.0
        return (self._serial(degree) * bytes_per_device / self._bw_unidir(axis)
                + self._lat(axis))


class TorusMachineModel(SimpleMachineModel):
    """Slice-topology-aware model: mesh axes are assigned to torus
    dimensions; an axis folded over k torus dims gets k× link bandwidth
    (reference analog: NetworkedMachineModel's topology matrix + routing,
    simulator.h:421-499, network.cc).

    ``axis_links``: mesh-axis name → number of torus links serving it
    (e.g. on a v4 4x4x4 slice with mesh {data:16, model:4}: the model axis
    mapped to one torus ring gets 1, data folded over two torus dims 2).
    """

    def __init__(
        self,
        chip: TPUChipSpec,
        axis_degrees: Dict[str, int],
        axis_links: Optional[Dict[str, int]] = None,
        wraparound: bool = True,
    ):
        n = 1
        for d in axis_degrees.values():
            n *= d
        super().__init__(chip, n)
        self.axis_degrees = dict(axis_degrees)
        self.axis_links = dict(axis_links or {})
        self.wraparound = wraparound

    def _bw(self, axis: str) -> float:
        links = self.axis_links.get(axis, 1)
        dirs = 2.0 if self.wraparound else 1.0
        return self.chip.ici_link_bandwidth * links * dirs


class MultiSliceMachineModel(TorusMachineModel):
    """Multi-slice: one designated mesh axis (usually the outermost data
    axis) crosses DCN; everything else is ICI within a slice (reference
    analog: inter-node bandwidth in SimpleMachineModel / the NIC segments of
    EnhancedMachineModel)."""

    def __init__(self, chip, axis_degrees, dcn_axes: Tuple[str, ...] = ("data_dcn",), **kw):
        super().__init__(chip, axis_degrees, **kw)
        self.dcn_axes = tuple(dcn_axes)

    def _bw(self, axis: str) -> float:
        if axis in self.dcn_axes:
            return self.chip.dcn_bandwidth
        return super()._bw(axis)

    def _bw_unidir(self, axis: str) -> float:
        if axis in self.dcn_axes:
            return self.chip.dcn_bandwidth
        return super()._bw_unidir(axis)

    def _lat(self, axis: str) -> float:
        if axis in self.dcn_axes:
            return self.chip.dcn_latency
        return super()._lat(axis)


def load_machine_model(path: str) -> MachineModel:
    """Build a machine model from a JSON config file (reference:
    --machine-model-file + machine_config_example consumed by
    EnhancedMachineModel, src/runtime/machine_model.cc; selection
    model.cc:3678-3685).

    Schema::

        {
          "version": "simple" | "torus" | "multislice" | "networked",
          "chip": "v5e" | {"name": ..., "peak_bf16_flops": ..., ...},
          "num_devices": 8,                  # simple only
          "axis_degrees": {"data": 4, "model": 2},   # torus/multislice/networked
          "axis_links": {"data": 2},         # optional, torus/multislice
          "wraparound": true,                # optional
          "dcn_axes": ["data_dcn"],          # multislice/networked
          "topology": [4, 2],                # networked: torus chip grid
          "topology_wrap": [true, true],     # optional
          "device_order": [0, 1, ...]        # optional mesh->chip permutation
        }
    """
    import json

    with open(path) as f:
        cfg = json.load(f)
    try:
        return machine_model_from_config(cfg)
    except (ValueError, KeyError, TypeError) as e:
        # re-attach the file context for EVERY config-shaped failure
        # (unknown chip preset raises KeyError, bad chip fields
        # TypeError — not just ValueError)
        raise ValueError(f"{type(e).__name__}: {e} (from {path})") from e


def machine_model_from_config(cfg: Dict) -> MachineModel:
    """Build a machine model from an in-memory ``load_machine_model``
    schema dict (the launcher writes these per cohort —
    ``parallel/multihost.two_level_mesh_spec`` — and tests build them
    directly)."""
    chip_cfg = cfg.get("chip", "v5e")
    if isinstance(chip_cfg, str):
        chip = CHIP_PRESETS[chip_cfg]
    else:
        chip = TPUChipSpec(**chip_cfg)
    version = cfg.get("version", "simple")
    if version == "simple":
        return SimpleMachineModel(chip, int(cfg.get("num_devices", 1)))
    if version == "torus":
        return TorusMachineModel(
            chip, cfg["axis_degrees"], cfg.get("axis_links"),
            wraparound=bool(cfg.get("wraparound", True)))
    if version == "multislice":
        return MultiSliceMachineModel(
            chip, cfg["axis_degrees"],
            dcn_axes=tuple(cfg.get("dcn_axes", ["data_dcn"])),
            axis_links=cfg.get("axis_links"),
            wraparound=bool(cfg.get("wraparound", True)))
    if version == "networked":
        from .network import (NetworkedMachineModel, TorusTopology,
                              default_topology_for)

        axis_degrees = cfg["axis_degrees"]
        dcn_axes = tuple(cfg.get("dcn_axes", []))
        if "topology" in cfg:
            topo = TorusTopology(
                tuple(cfg["topology"]),
                tuple(cfg["topology_wrap"]) if "topology_wrap" in cfg else ())
        else:
            n = 1
            for a, d in axis_degrees.items():
                if a not in dcn_axes:
                    n *= d
            topo = default_topology_for(n)
        return NetworkedMachineModel(
            chip, topo, axis_degrees,
            device_order=cfg.get("device_order"), dcn_axes=dcn_axes)
    raise ValueError(f"unknown machine model version {version!r}")


def multihost_machine_model(num_processes: int, devices_per_process: int,
                            model_degree: int = 1,
                            chip: str = "v5e") -> MachineModel:
    """The cohort's two-level pricing model: a
    :class:`MultiSliceMachineModel` whose composed ``data`` axis is
    priced at DCN bandwidth while any ``model`` axis stays on ICI —
    built from the same plan the launcher's workers feed the search
    (``parallel/multihost.two_level_mesh_spec``), so simulator pricing
    and the executed layout can never drift apart."""
    from ..parallel.multihost import two_level_mesh_spec

    return machine_model_from_config(two_level_mesh_spec(
        num_processes, devices_per_process, model_degree=model_degree,
        chip=chip)["machine_model"])


# ``jax.devices()[0].device_kind`` as each chip reports it -> preset. A
# kind that is not here is an error, never a default: a search priced
# for the wrong chip is a wrong plan with nothing to show for it.
DEVICE_KIND_PRESETS: Dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}


def detect_machine_model(n_devices: Optional[int] = None) -> MachineModel:
    """The machine model of the platform JAX is running on (reference
    analog: FFConfig querying the Realm machine, model.cc:3501). Raises
    ``ValueError`` on an accelerator whose ``device_kind`` has no preset."""
    import jax

    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if devs[0].platform == "cpu":
        # a virtual CPU mesh (xla_force_host_platform_device_count): the
        # "devices" time-slice one socket — model it honestly so the
        # search picks strategies that actually help HERE (usually: none)
        return SimpleMachineModel(CHIP_PRESETS["cpu-host"], n,
                                  shared_host=True)
    kind = devs[0].device_kind
    if kind not in DEVICE_KIND_PRESETS:
        raise ValueError(
            f"no chip preset for device_kind {kind!r} (platform "
            f"{devs[0].platform!r}); known: {sorted(DEVICE_KIND_PRESETS)}. "
            f"Add its public figures to CHIP_PRESETS and its device_kind "
            f"to DEVICE_KIND_PRESETS, or pass --machine-model-file.")
    return SimpleMachineModel(CHIP_PRESETS[DEVICE_KIND_PRESETS[kind]], n)
