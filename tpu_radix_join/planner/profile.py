"""Device profiles: versioned, cited calibration constants.

A profile is a small JSON document holding every hardware constant the cost
model (planner/cost_model.py) consumes.  Two rules keep it honest:

  * **Versioned schema** — ``schema_version`` gates compatibility; loading
    a newer schema than this code understands raises instead of guessing.
  * **Cited constants** — every constant is ``{"value": x, "source": tag}``
    where the tag names the measurement it came from (a PERF_NOTES table,
    a chip artifact path, or a ``calibrate:`` microbenchmark).  A constant
    without a source is rejected at load time, and a tier-1 test walks
    :data:`REQUIRED_CONSTANTS` so the stage model can never silently grow
    an uncited coefficient (tests/test_planner.py).

The checked-in ``profiles/v5e_lite.json`` holds the TPU v5e constants, each
citing its measurement, calibration run or "not measured";
:func:`calibrate` refreshes the refreshable subset from on-device
microbenchmarks, and ``tools_make_report.py --emit-profile`` distills a
round's chip artifacts into a profile the same way.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Dict, Optional

# v2 adds ``ici_bytes_per_s`` — the exchange constant the codec-aware
# wire-time term consumes (cost_model.plan_exchange).  v1 profiles load
# through a shim that derives it from the cited ``ici_gbps`` (see
# load_profile), so old files keep working without edits.
# v3 adds per-constant *provenance*: a constant entry may carry a
# ``"provenance"`` dict next to its value/source — fit origin, ledger run
# ids, sample count, 95% confidence interval, fit residual, and a
# freshness timestamp (planner/calibrate.py writes these).  v1/v2 files
# load unchanged (provenance is additive; absent means "committed
# snapshot, citation in the source tag").
# v4 adds ``partition_pass_unit_ms`` — ms per million tuples per streaming
# pass of the fused Pallas radix-partition kernel (ops/pallas/partition.py;
# the kernel makes two passes over the ids and the lanes cross HBM twice).
# v1-v3 profiles load through a shim deriving it from the cited hbm_gbps
# (8 B of ids traffic per tuple per pass at streaming bandwidth).
# v5 added the per-digit-pass price of a Pallas radix sort the pipeline
# no longer has; a file that still carries it loads, and nothing reads it.
# v6 adds ``result_cache_lookup_ms`` — the host-side price of one
# fingerprint + LRU probe of the serving result cache
# (service/resultcache.py; the planner's serve_cached strategy row is
# this constant alone).  v1-v5 profiles load through a shim deriving it
# as dispatch_floor_ms / 10 — a pure-host hash lookup is at least an
# order of magnitude under one device round trip.
SCHEMA_VERSION = 6

#: Constants the cost model reads.  Adding a term to cost_model.py means
#: adding its constant here AND to every shipped profile, with a source tag
#: — the conftest-level citation check enforces the pairing.
REQUIRED_CONSTANTS = (
    # XLA sort emitter cost: ms per stage-unit at the 33.5M reference size
    # (stage model: t = unit * (M / 33.5M) * U(M), U = k(k+1)/2)
    "sort_stage_unit_ms",
    # measured penalty of the 2-key lexicographic (full-range) sort vs the
    # packed single-lane sort at equal element count
    "full_range_sort_factor",
    # per-program host dispatch round-trip floor (does not pipeline)
    "dispatch_floor_ms",
    # sustained HBM bandwidth of one elementwise pass (r+w)
    "hbm_gbps",
    # device memory envelope the in-core engine may occupy
    "hbm_bytes",
    # block-scatter loop discipline: sustained M elements/s of the
    # per-destination DMA-slice permutation (the only fast dest-grouping
    # engine; the one-shot gather is the measured ~24x cliff)
    "scatter_loop_melems_s",
    # random-gather rate, the cliff side of the same measurement
    "gather_melems_s",
    # per-chip interconnect bandwidth the all_to_all shuffle rides
    "ici_gbps",
    # the same link expressed in bytes/s — the unit the codec-aware wire
    # time consumes (wire_ms = wire_bytes / ici_bytes_per_s * 1e3, with
    # wire_bytes taken from the packed WireSpec, not a hardcoded 8 B/tuple).
    # Schema v2; v1 profiles are shimmed to ici_gbps * 1e9 at load.
    "ici_bytes_per_s",
    # fused Pallas radix-partition kernel: ms per million tuples per
    # streaming pass (the kernel is two passes over the ids; the cost model
    # charges unit * Mtuples * 2).  Schema v4; v1-v3 profiles are shimmed
    # to 8.0 / hbm_gbps at load (4 B read + 4 B written per tuple per pass
    # at the profile's streaming bandwidth).
    "partition_pass_unit_ms",
    # serving result cache: ms per fingerprint + LRU probe on the host
    # (service/resultcache.py — sha256 over the canonical request spec
    # plus one OrderedDict move-to-end; no device work at all).  The
    # serve_cached strategy row is this constant alone, which is what
    # makes the planner prefer it over every execution arm.  Schema v6;
    # v1-v5 profiles are shimmed to dispatch_floor_ms / 10 at load.
    "result_cache_lookup_ms",
)

#: Reference element count of the sort stage model's unit (PERF_NOTES
#: round 2: 0.147 ms/stage-unit measured at the 33.5M packed union).
SORT_REF_ELEMS = 33_554_432


class ProfileError(ValueError):
    """Malformed, uncited, or incompatible profile document."""


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Immutable view of one device's calibration constants."""

    name: str
    constants: Dict[str, dict]          # key -> {"value": float, "source": str}
    schema_version: int = SCHEMA_VERSION
    notes: str = ""

    def __post_init__(self):
        if self.schema_version > SCHEMA_VERSION:
            raise ProfileError(
                f"profile {self.name!r} has schema_version "
                f"{self.schema_version}; this build understands "
                f"<= {SCHEMA_VERSION}")
        for key in REQUIRED_CONSTANTS:
            if key not in self.constants:
                raise ProfileError(
                    f"profile {self.name!r} is missing constant {key!r}")
        for key, entry in self.constants.items():
            if (not isinstance(entry, dict) or "value" not in entry
                    or not str(entry.get("source", "")).strip()):
                raise ProfileError(
                    f"profile {self.name!r} constant {key!r} must be "
                    f"{{'value': ..., 'source': <measurement tag>}} — an "
                    f"uncited constant cannot be audited against chip logs")

    def value(self, key: str) -> float:
        try:
            return float(self.constants[key]["value"])
        except KeyError:
            raise ProfileError(
                f"profile {self.name!r} has no constant {key!r}") from None

    def source(self, key: str) -> str:
        return str(self.constants[key]["source"])

    def provenance(self, key: str) -> Optional[dict]:
        """The schema-v3 provenance block of one constant (run ids, sample
        count, CI, residual, freshness), or None for a committed/v1/v2
        entry that carries only its citation string."""
        entry = self.constants.get(key) or {}
        prov = entry.get("provenance")
        return dict(prov) if isinstance(prov, dict) else None

    def freshness(self) -> Optional[float]:
        """Newest ``fitted_at_epoch_s`` across the constants' provenance
        blocks — what ``--profile auto`` compares against its freshness
        window.  None when no constant was ever fitted."""
        stamps = [p["fitted_at_epoch_s"]
                  for p in (self.provenance(k) for k in self.constants)
                  if p and isinstance(p.get("fitted_at_epoch_s"),
                                      (int, float))]
        return max(stamps) if stamps else None

    def fingerprint(self) -> dict:
        """Stable identity for cache keys / multi-host manifests: a plan or
        capacity cached under one profile must never warm-start a run under
        different constants."""
        return {"name": self.name, "schema_version": self.schema_version,
                "constants": {k: self.constants[k]["value"]
                              for k in sorted(self.constants)}}

    def to_dict(self) -> dict:
        return {"schema_version": self.schema_version, "name": self.name,
                "notes": self.notes, "constants": self.constants}

    def save(self, path: str) -> str:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    def replace_constants(self, name: Optional[str] = None,
                          **updates: dict) -> "DeviceProfile":
        """New profile with some constants replaced (each update a full
        ``{"value", "source"}`` entry — recalibration never drops a
        citation)."""
        merged = {**self.constants, **updates}
        return dataclasses.replace(self, name=name or self.name,
                                   constants=merged)


def _profiles_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "profiles")


def load_profile(name_or_path: str = "v5e_lite") -> DeviceProfile:
    """Load a profile by bare name (resolved against the packaged
    ``profiles/`` directory) or by explicit JSON path."""
    path = name_or_path
    if not os.path.exists(path):
        candidate = os.path.join(_profiles_dir(), f"{name_or_path}.json")
        if os.path.exists(candidate):
            path = candidate
        else:
            raise ProfileError(
                f"no profile {name_or_path!r}: not a file, and "
                f"{candidate} does not exist")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ProfileError(f"unreadable profile {path}: {e!r}") from e
    try:
        constants = dict(doc["constants"])
        version = int(doc.get("schema_version", 1))
        if version < 2 and "ici_bytes_per_s" not in constants:
            # schema-v1 shim: the codec-aware wire time (schema v2) reads
            # ici_bytes_per_s; derive it from the v1 profile's cited
            # ici_gbps so old files load unchanged.  The source tag records
            # the derivation, keeping the citation chain auditable.
            entry = constants.get("ici_gbps")
            if isinstance(entry, dict) and "value" in entry:
                constants["ici_bytes_per_s"] = {
                    "value": float(entry["value"]) * 1e9,
                    "source": ("shim:derived from ici_gbps "
                               "(schema v1 profile; "
                               f"{entry.get('source', 'uncited')})")}
        if version < 4 and "partition_pass_unit_ms" not in constants:
            # schema v1-v3 shim: the partition cost term (schema v4) reads
            # partition_pass_unit_ms; derive it from the cited hbm_gbps —
            # one kernel pass streams 4 B of ids in + 4 B of slots out per
            # tuple, so at bandwidth B GB/s a million tuples cost 8e6/B ns
            # = 8/B ms.
            entry = constants.get("hbm_gbps")
            if isinstance(entry, dict) and entry.get("value"):
                constants["partition_pass_unit_ms"] = {
                    "value": round(8.0 / float(entry["value"]), 5),
                    "source": ("shim:derived from hbm_gbps "
                               f"(schema v{version} profile; "
                               f"{entry.get('source', 'uncited')})")}
        if version < 6 and "result_cache_lookup_ms" not in constants:
            # schema v1-v5 shim: the serve_cached strategy row (schema v6)
            # reads result_cache_lookup_ms; derive it from the cited
            # dispatch_floor_ms — a host-side hash probe touches no device,
            # so a tenth of the dispatch round trip is a conservative
            # ceiling (the measured v5e_lite value is far smaller still).
            entry = constants.get("dispatch_floor_ms")
            if isinstance(entry, dict) and entry.get("value"):
                constants["result_cache_lookup_ms"] = {
                    "value": round(float(entry["value"]) / 10.0, 5),
                    "source": ("shim:derived from dispatch_floor_ms "
                               f"(schema v{version} profile; "
                               f"{entry.get('source', 'uncited')})")}
        return DeviceProfile(
            name=doc["name"], constants=constants,
            schema_version=version,
            notes=doc.get("notes", ""))
    except KeyError as e:
        raise ProfileError(f"profile {path} missing field {e}") from e


#: filename the fitter writes next to a ledger; what ``--profile auto``
#: prefers over the committed snapshot while it is fresh
FITTED_PROFILE_BASENAME = "profile_fitted.json"
DEFAULT_PROFILE = "v5e_lite"

#: how old a fitted profile may be before ``auto`` falls back to the
#: committed snapshot (override: TPU_RADIX_PROFILE_FRESH_S)
DEFAULT_FRESH_S = 30 * 86400.0


def resolve_profile(spec: str, ledger_dir: Optional[str] = None,
                    fresh_s: Optional[float] = None) -> str:
    """Resolve the driver's ``--profile`` value.  Anything but ``auto``
    passes through.  ``auto`` prefers ``<ledger_dir>/profile_fitted.json``
    (planner/calibrate.py output) when it loads AND its newest fit is
    within the freshness window; otherwise the committed snapshot.  The
    decision is returned as a loadable name-or-path — callers print it so
    a run's profile choice is never silent."""
    if spec != "auto":
        return spec
    if ledger_dir is None:
        from tpu_radix_join.observability.ledger import default_ledger_dir
        ledger_dir = default_ledger_dir()
    if fresh_s is None:
        fresh_s = float(os.environ.get("TPU_RADIX_PROFILE_FRESH_S",
                                       DEFAULT_FRESH_S))
    candidate = os.path.join(ledger_dir, FITTED_PROFILE_BASENAME)
    if os.path.exists(candidate):
        try:
            fitted_at = load_profile(candidate).freshness()
        except ProfileError:
            return DEFAULT_PROFILE     # an unloadable fit never wins
        if fitted_at is not None and time.time() - fitted_at <= fresh_s:
            return candidate
    return DEFAULT_PROFILE


def format_provenance(profile: DeviceProfile,
                      stale: Optional[dict] = None,
                      now_s: Optional[float] = None) -> str:
    """Per-constant provenance/staleness table — the constants half of the
    ``--plan explain`` output.  ``stale`` is planner/calibrate.py's
    ``detect_stale`` result (or any mapping/iterable of constant names);
    a flagged constant's row says STALE and names the drift that
    indicted it."""
    stale = stale or {}
    now_s = time.time() if now_s is None else now_s
    header = ["constant", "value", "origin", "n", "ci95", "residual",
              "age_h", "stale", "runs"]
    rows = []
    for key in sorted(profile.constants):
        prov = profile.provenance(key) or {}
        origin = (prov.get("origin")
                  or profile.source(key).split(":", 1)[0] or "committed")
        n = prov.get("n")
        ci = prov.get("ci95")
        resid = prov.get("residual")
        ts = prov.get("fitted_at_epoch_s")
        runs = prov.get("runs") or []
        runs_cell = ",".join(str(r) for r in runs)
        if len(runs_cell) > 40:
            runs_cell = runs_cell[:37] + "..."
        cell = ""
        if key in stale:
            info = stale[key] if isinstance(stale, dict) else None
            cell = "STALE"
            if isinstance(info, dict) and info.get("mean_drift_pct"):
                cell += f" ({info['mean_drift_pct']:.0f}% drift)"
        rows.append([
            key, f"{profile.value(key):g}", str(origin),
            str(n) if n else "-",
            (f"[{ci[0]:g}, {ci[1]:g}]"
             if isinstance(ci, (list, tuple)) and len(ci) == 2 else "-"),
            f"{resid:.3f}" if isinstance(resid, (int, float)) else "-",
            (f"{(now_s - ts) / 3600:.1f}"
             if isinstance(ts, (int, float)) else "-"),
            cell, runs_cell])
    widths = [max(len(header[i]), *(len(r[i]) for r in rows))
              for i in range(len(header))]
    fmt = lambda cells: "| " + " | ".join(
        c.ljust(widths[i]) for i, c in enumerate(cells)) + " |"
    lines = [f"profile {profile.name} (schema v{profile.schema_version}) "
             f"constants — provenance/staleness:",
             fmt(header),
             "|" + "|".join("-" * (w + 2) for w in widths) + "|"]
    lines += [fmt(r) for r in rows]
    flagged = [k for k in sorted(profile.constants) if k in stale]
    if flagged:
        lines.append(f"stale: {', '.join(flagged)} — re-fit with "
                     f"tools_profile_fit.py refresh")
    return "\n".join(lines)


def sort_stage_units(elems: int) -> float:
    """U(M) = k(k+1)/2 for k = ceil(log2 M): the XLA sort emitter's
    stage-count term, validated to <1% against the measured flat-sort
    times at 16M/33.5M (PERF_NOTES round 3 'sort floor, quantified')."""
    if elems <= 1:
        return 1.0
    k = math.ceil(math.log2(elems))
    return k * (k + 1) / 2


def calibrate(base: Optional[DeviceProfile] = None,
              name: Optional[str] = None,
              sort_elems: int = 1 << 21) -> DeviceProfile:
    """Refresh the microbenchmark-measurable constants on the current JAX
    backend; constants with no cheap on-device probe (memory envelope when
    the backend hides it) keep the base profile's cited values.

    Methodology: amortized async dispatches closed by one
    ``block_until_ready``, compile excluded.  The close copies nothing to
    the host: a readback of the output would time the host link, not the
    device.  The elementwise pass moves 2^27
    uint32 each way (1 GiB of traffic), so each dispatch's kernel outlasts
    the sub-ms dispatch floor and the pass measures bandwidth, not the
    floor.  Sources are tagged
    ``calibrate:<benchmark>`` so a calibrated profile is distinguishable
    from the committed chip tables at a glance.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    base = base or load_profile()

    def timed(fn, *args, iters=10):
        jax.block_until_ready(fn(*args))          # compile warmup
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    updates = {}
    # HBM envelope: one elementwise pass, read+write
    hbm_elems = 1 << 27
    x = jnp.arange(hbm_elems, dtype=jnp.uint32)
    dt = timed(jax.jit(lambda a: a + jnp.uint32(1)), x)
    del x
    updates["hbm_gbps"] = {"value": round(2 * 4 * hbm_elems / dt / 1e9, 2),
                           "source": "calibrate:elementwise_pass"}
    # sort emitter stage unit, normalized to the 33.5M reference size
    keys = jnp.asarray(np.random.default_rng(0).integers(
        0, 1 << 31, sort_elems, dtype=np.uint32))
    dt = timed(jax.jit(lambda a: jax.lax.sort(a, is_stable=False)), keys)
    unit = dt * 1e3 / (sort_elems / SORT_REF_ELEMS) / sort_stage_units(
        sort_elems)
    updates["sort_stage_unit_ms"] = {
        "value": round(unit, 5),
        "source": "calibrate:flat_sort impl=xla(jax.lax.sort)"}
    # dispatch floor: the trivial-program round trip
    tiny = jnp.zeros((8,), jnp.uint32)
    fn = jax.jit(lambda a: a + jnp.uint32(1))
    jax.block_until_ready(fn(tiny))
    t0 = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(fn(tiny))
    updates["dispatch_floor_ms"] = {
        "value": round((time.perf_counter() - t0) / 20 * 1e3, 3),
        "source": "calibrate:empty_dispatch"}
    # memory envelope, where the backend reports it
    stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
    if stats and stats.get("bytes_limit"):
        updates["hbm_bytes"] = {"value": int(stats["bytes_limit"]),
                                "source": "calibrate:memory_stats"}
    return base.replace_constants(
        name=name or f"{base.name}+calibrated", **updates)
