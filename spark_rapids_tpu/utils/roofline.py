"""The ONE roofline table every instrument judges against.

Before this module the engine carried two diverging ceilings: the
movement ledger's hard-coded per-edge ``NOMINAL_GBPS`` dict
(utils/movement.py) and whatever number a bench happened to probe.
Adding kernel-level attribution (utils/kernelprof.py) would have made
it three.  Instead, every bandwidth/compute ceiling now resolves here,
and every entry is conf-overridable under
``spark.rapids.sql.profile.roofline.*`` — so an operator who probes
real hardware sets the ceilings once and BOTH the movement report's
per-edge utilization and kernelprof's achieved-GFLOP/s / GB/s roofline
percentages judge against the same numbers.

Edge ceilings use the movement ledger's edge names (upload / readback /
spill / wire / collective); the compute side adds the HBM bandwidth
ceiling and the peak-GFLOP/s ceiling the per-kernel roofline join
needs.  The two device ceilings (HBM GB/s, peak GFLOP/s) come from
`DEVICE_PEAKS`, keyed by the `device_kind` JAX reports, unless the conf
overrides them; a device that is not in the table has NO ceiling, and
the roofline shares judged against it are None, never a number graded
against some other chip.
"""
from __future__ import annotations

from typing import Optional

from spark_rapids_tpu import config as C

#: movement-ledger edge name -> its roofline conf entry
_EDGE_CONFS = {
    "upload": C.ROOFLINE_UPLOAD_GBPS,
    "readback": C.ROOFLINE_READBACK_GBPS,
    "spill": C.ROOFLINE_SPILL_GBPS,
    "wire": C.ROOFLINE_WIRE_GBPS,
    "collective": C.ROOFLINE_COLLECTIVE_GBPS,
}

#: registry defaults, importable without a conf in hand (the movement
#: ledger's legacy NOMINAL_GBPS view aliases this)
DEFAULT_EDGE_GBPS = {edge: e.default for edge, e in _EDGE_CONFS.items()}


def _conf(conf: Optional[C.RapidsConf]) -> C.RapidsConf:
    return conf if conf is not None else C.get_active_conf()


def edge_gbps(edge: str, conf: Optional[C.RapidsConf] = None) -> float:
    """Bandwidth ceiling (GB/s) for one movement-ledger edge.  The
    legacy all-edges override (profile.movement.rooflineGBps, non-zero)
    wins over the per-edge entries so probed-hardware workflows that
    predate the shared table keep working."""
    conf = _conf(conf)
    override = float(conf[C.MOVEMENT_ROOFLINE_GBPS])
    if override > 0:
        return override
    entry = _EDGE_CONFS.get(edge)
    return float(conf[entry]) if entry is not None else 0.0


def edge_table(conf: Optional[C.RapidsConf] = None) -> dict:
    """{edge: ceiling GB/s} for every movement edge under `conf`."""
    return {edge: edge_gbps(edge, conf) for edge in _EDGE_CONFS}


#: nominal per-chip peaks by `jax.devices()[0].device_kind`.
#: "TPU v5 lite" is the v5e — source: Google Cloud documentation,
#: "TPU v5e": 819 GB/s of HBM bandwidth, 197 TFLOP/s in bf16.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "peak_gflops": 197000.0},
}


def _device_peak(entry: C.ConfEntry, name: str,
                 conf: Optional[C.RapidsConf]) -> Optional[float]:
    override = float(_conf(conf)[entry])
    if override > 0:
        return override
    import jax
    peaks = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    return None if peaks is None else peaks[name]


def hbm_gbps(conf: Optional[C.RapidsConf] = None) -> Optional[float]:
    """HBM bandwidth ceiling (GB/s) for the per-kernel memory-bound
    roofline fraction (XLA bytes-accessed / device time vs this); None
    on a device with no entry in DEVICE_PEAKS and no conf override."""
    return _device_peak(C.ROOFLINE_HBM_GBPS, "hbm_gbps", conf)


def peak_gflops(conf: Optional[C.RapidsConf] = None) -> Optional[float]:
    """Compute ceiling (GFLOP/s) for the per-kernel compute-bound
    roofline fraction; None like `hbm_gbps`."""
    return _device_peak(C.ROOFLINE_PEAK_GFLOPS, "peak_gflops", conf)
