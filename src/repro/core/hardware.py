"""Hardware profiles for the ASA cost model and roofline analysis.

TPU_V5E is the deployment target (roofline constants per the spec);
V100_CLUSTER reproduces the paper's own 8-GPU setting for Table I validation.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    name: str
    peak_flops: float          # per chip, bf16/fp16 FLOP/s
    hbm_bw: float              # per chip, bytes/s
    link_bw: float             # per link, bytes/s (ICI / NVLink)
    hbm_bytes: float           # per chip HBM capacity
    # inter-pod (DCN) bandwidth per host, bytes/s; 0 => single-pod only
    dcn_bw: float = 0.0
    # fraction of peak realistically achievable on large matmuls (MFU ceiling
    # used by the *cost model*, not the roofline — roofline uses raw peak)
    matmul_efficiency: float = 0.6


TPU_V5E = HardwareProfile(
    name="tpu_v5e",
    peak_flops=197e12,         # bf16
    hbm_bw=819e9,
    link_bw=50e9,              # ~50 GB/s per ICI link
    hbm_bytes=16e9,
    dcn_bw=25e9,
    matmul_efficiency=0.6,
)

# jax's ``device_kind`` of each chip this repo has a profile for
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def profile_for_device_kind(kind: str) -> HardwareProfile:
    if kind not in DEVICE_KINDS:
        raise ValueError(f"no hardware profile for device_kind {kind!r}; "
                         f"known: {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]


def local_profile() -> HardwareProfile:
    """The attached TPU's profile, looked up by ``device_kind``.  Off a TPU
    (CPU tests, the dry run's virtual devices) plans target TPU_V5E, the
    deployment chip."""
    import jax
    if jax.default_backend() != "tpu":
        return TPU_V5E
    return profile_for_device_kind(jax.devices()[0].device_kind)


V100_CLUSTER = HardwareProfile(
    name="v100_nvlink",
    peak_flops=125e12,         # fp16 tensor core
    hbm_bw=900e9,
    link_bw=25e9,              # NVLink2 per direction per link
    hbm_bytes=32e9,
    dcn_bw=0.0,
    matmul_efficiency=0.45,    # V100-era utilization on 25M-86M param models
)


def ring_allreduce_time(bytes_: float, n: int, link_bw: float) -> float:
    """Bandwidth-optimal ring all-reduce: 2*(n-1)/n * bytes / link_bw."""
    if n <= 1 or bytes_ == 0:
        return 0.0
    return 2.0 * (n - 1) / n * bytes_ / link_bw


def allgather_time(bytes_out: float, n: int, link_bw: float) -> float:
    """Ring all-gather of a full tensor of `bytes_out` total size."""
    if n <= 1 or bytes_out == 0:
        return 0.0
    return (n - 1) / n * bytes_out / link_bw


def reducescatter_time(bytes_in: float, n: int, link_bw: float) -> float:
    if n <= 1 or bytes_in == 0:
        return 0.0
    return (n - 1) / n * bytes_in / link_bw


def alltoall_time(bytes_: float, n: int, link_bw: float) -> float:
    if n <= 1 or bytes_ == 0:
        return 0.0
    return (n - 1) / n * bytes_ / link_bw
