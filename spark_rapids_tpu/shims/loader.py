"""Shim discovery (reference `ShimLoader.scala:26-61`).

The reference finds `SparkShimServiceProvider`s via Java's `ServiceLoader`
and picks the one whose `matchesVersion` accepts the running Spark version
(with a Databricks sniff, since Databricks misreports its base version).
Here providers self-register at import; resolution is by exact version
string, with the same Databricks detection hook.
"""
from __future__ import annotations

import logging
import threading
from typing import Optional

from spark_rapids_tpu import config as C
from spark_rapids_tpu.shims.base import SparkShims
from spark_rapids_tpu.shims.versions import ALL_SHIMS

log = logging.getLogger(__name__)

_PROVIDERS: list[type] = list(ALL_SHIMS)
_lock = threading.Lock()
_cache: dict[str, SparkShims] = {}


def register_provider(shim_class: type) -> None:
    """ServiceLoader analog: add an externally-defined shim provider.
    Prepended so an external provider can override a built-in version."""
    with _lock:
        _PROVIDERS.insert(0, shim_class)
        _cache.clear()


def _has_provider(version: str) -> bool:
    with _lock:
        return any(version in p.VERSION_NAMES for p in _PROVIDERS)


def detect_version(conf: Optional[C.RapidsConf] = None) -> str:
    """The session's Spark version.  Databricks detection mirrors
    `ShimLoader.scala`: the cluster-tag conf marks a Databricks runtime
    regardless of the reported base version — but only when a Databricks
    shim for that base version exists, so an unexpected runtime degrades
    to the upstream shim instead of failing every plan rewrite."""
    conf = conf or C.get_active_conf()
    version = str(conf[C.SPARK_VERSION])
    if conf.get("spark.databricks.clusterUsageTags.clusterId") \
            and "databricks" not in version:
        db = f"{version}-databricks"
        if _has_provider(db):
            return db
        log.warning(
            "Databricks runtime detected but no %s shim exists; "
            "using the upstream %s shim", db, version)
    return version


def _nearest_minor(version: str) -> Optional[str]:
    """Highest known patch release within the same major.minor line
    (e.g. an unknown 3.0.9 -> 3.0.2).  Databricks-suffixed versions
    never cross-match — their drift is runtime-wide, not patch-level."""
    if "databricks" in version:
        return None
    parts = version.split(".")
    if len(parts) < 2:
        return None
    prefix = ".".join(parts[:2]) + "."
    with _lock:
        known = [v for p in _PROVIDERS for v in p.VERSION_NAMES
                 if v.startswith(prefix) and "databricks" not in v]
    if not known:
        return None
    # numeric ordering: lexicographic would rank 3.0.2 above 3.0.10
    import re
    return max(known,
               key=lambda v: [int(x) for x in re.findall(r"\d+", v)])


def get_spark_shims(version: Optional[str] = None,
                    conf: Optional[C.RapidsConf] = None) -> SparkShims:
    conf = conf or C.get_active_conf()
    version = version or detect_version(conf)
    with _lock:
        hit = _cache.get(version)
        if hit is None and conf[C.ALLOW_UNKNOWN_SPARK_VERSION]:
            # fallback results live under a gated key (see below)
            hit = _cache.get(version + "|fallback")
        if hit is not None:
            return hit
        for provider in _PROVIDERS:
            if version in provider.VERSION_NAMES:
                shims = provider()
                _cache[version] = shims
                log.info("Loaded shims for Spark %s via %s", version,
                         provider.__name__)
                return shims
    # unknown version: the reference ShimLoader throws here (a new
    # Spark release needs a new shim — silent use of a stale one can
    # miscompile plans).  Conf-gated escape hatch for operators who
    # accept that risk: fall back to the nearest same-minor shim with
    # a loud warning (the arrival of a new version has a defined,
    # tested behavior either way).
    near = _nearest_minor(version)
    if near is not None and conf[C.ALLOW_UNKNOWN_SPARK_VERSION]:
        log.warning(
            "No shim provider for Spark %s; "
            "spark.rapids.tpu.allowUnknownSparkVersion is set — "
            "falling back to the %s shim. Version-sensitive "
            "behaviors (rebase defaults, First/Last API, AQE "
            "reader specs) follow %s, which may be WRONG for %s.",
            version, near, near, version)
        shims = get_spark_shims(near)
        # cached under a FALLBACK-ONLY key: a later session with the
        # gate unset must still get the documented RuntimeError, not a
        # silently cached fallback shim
        with _lock:
            _cache[version + "|fallback"] = shims
        return shims
    hint = (f" (set {C.ALLOW_UNKNOWN_SPARK_VERSION.key} to fall back "
            f"to the {near} shim at your own risk)"
            if near is not None
            and not conf[C.ALLOW_UNKNOWN_SPARK_VERSION] else "")
    raise RuntimeError(
        f"Could not find a shim provider for Spark version {version!r}; "
        f"supported: "
        f"{[v for p in _PROVIDERS for v in p.VERSION_NAMES]}{hint}")


def current_shims(conf: Optional[C.RapidsConf] = None) -> SparkShims:
    return get_spark_shims(conf=conf)
