"""Persistent plan cache: tuned once, served forever.

The paper pays hours of place-and-route per configuration and therefore
tunes offline, shipping the chosen bitstream; our analogue is a JSON cache
of tuned plans so serving paths (`configs/*`, `benchmarks/*`, `launch/*`)
get the winning (block_shape, par_time, backend) with zero search cost.

Keying — a cache entry is addressed by the sha1 of:

  * the program fingerprint: every ``StencilProgram`` field, canonically
    ordered (two equal programs share tuned plans; any semantic change
    misses);
  * the measurement grid shape (blocking quality is grid-dependent);
  * the chip name (plans do not transfer across hardware);
  * the backend name **and registry version** — a version bump (a new
    lowering registered for the same name) invalidates every plan tuned
    through the old lowering, the whole point of the versioned registry;
  * ``SCHEMA_VERSION`` of the tuner itself (a model/space change
    invalidates the world).

Writes are atomic (tmp file + ``os.replace``) so concurrent tuners can at
worst lose a plan, never corrupt the file.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
from typing import Dict, Optional, Tuple

from repro.core.program import as_program

# 2: measurements now time steady-state fused multi-superstep runs (the
#    donated run executor) instead of lone superstep dispatches, and the
#    pipelined kernel variant became a searchable backend axis — records
#    tuned under schema 1 measured a different quantity and must miss.
# 3: the space gained a mesh-decomposition axis and the key a ``decomp``
#    component; schema-2 records were tuned over a space with no
#    decomposition dimension (and no per-shard halo pruning) and must miss.
# 4: the kernel variant (plain/pipelined/temporal) became a first-class
#    searchable axis: candidates and records carry ``variant``, the key a
#    ``variant`` request component, and ranking is variant-aware (the
#    temporal chunk's amortized traffic/compute) — schema-3 records ranked
#    temporal-free spaces under a variant-blind model and must miss.
# 5: compiled plans are charged the frame the compiled kernel DMAs and
#    sweeps at every fused step (tile-rounded ring, no shrinking region,
#    no carry pass), and the overlap-tax prune uses that fraction —
#    schema-4 winners were ranked by the exact-halo trapezoid and must miss.
SCHEMA_VERSION = 5

ENV_CACHE_PATH = "REPRO_TUNING_CACHE"
_DEFAULT_PATH = os.path.join("~", ".cache", "repro-stencil", "plans.json")


def default_cache_path() -> str:
    return os.path.expanduser(os.environ.get(ENV_CACHE_PATH, _DEFAULT_PATH))


def program_fingerprint(program) -> str:
    """Canonical digest of every program field (order-independent)."""
    prog = as_program(program)
    payload = json.dumps(dataclasses.asdict(prog), sort_keys=True)
    return hashlib.sha1(payload.encode()).hexdigest()


def cache_key(program, grid_shape: Tuple[int, ...], chip_name: str,
              backend: str, backend_version: int,
              decomp: Optional[object] = None,
              variant: Optional[str] = None) -> str:
    """``decomp`` identifies the decomposition *request*: None (single
    device), an explicit per-axis shard tuple, or the ``"ndev=N"`` marker
    for a free search over N devices — three different search spaces, three
    different keys (a plan tuned for one mesh layout must never serve
    another).  ``variant`` likewise identifies the kernel-variant *request*
    (None = backend pinned as given, ``"auto"`` = search every registered
    sibling, or a concrete variant name): different policies search
    different spaces, so their winners never serve each other."""
    payload = json.dumps({
        "program": program_fingerprint(program),
        "grid_shape": list(grid_shape),
        "chip": chip_name,
        "backend": backend,
        "backend_version": backend_version,
        "decomp": list(decomp) if isinstance(decomp, (tuple, list))
        else decomp,
        "variant": variant,
        "schema": SCHEMA_VERSION,
    }, sort_keys=True)
    return hashlib.sha1(payload.encode()).hexdigest()


class PlanCache:
    """Dict-of-JSON-records plan store. Values are plain dicts produced by
    ``tuning.autotune`` (see ``TunedPlan.to_record``); the cache itself is
    schema-agnostic beyond the top-level ``{key: record}`` layout."""

    def __init__(self, path: Optional[str] = None):
        self.path = os.path.expanduser(path) if path else default_cache_path()

    # -- storage ------------------------------------------------------------

    def _load(self) -> Dict[str, dict]:
        try:
            with open(self.path) as f:
                data = json.load(f)
            return data if isinstance(data, dict) else {}
        except (FileNotFoundError, json.JSONDecodeError):
            return {}

    def _store(self, data: Dict[str, dict]) -> None:
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".plans-", suffix=".json", dir=d)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise

    # -- API ----------------------------------------------------------------

    def get(self, key: str) -> Optional[dict]:
        """Single-record view: the most recently added record under key."""
        records = self.get_all(key)
        return records[-1] if records else None

    def get_all(self, key: str) -> list:
        """Every record under key (a key holds one record *per search
        bounds* — see :meth:`add`)."""
        v = self._load().get(key)
        if v is None:
            return []
        return list(v) if isinstance(v, list) else [v]

    def put(self, key: str, record: dict) -> None:
        """Replace everything under key with one record."""
        data = self._load()
        data[key] = record
        self._store(data)

    def add(self, key: str, record: dict) -> None:
        """Append a record under key, replacing any record with the same
        ``search`` bounds.  Keeping one record per bounds (rather than one
        per key) stops consumers that tune the same program/grid under
        different bounds from evicting each other on every call."""
        data = self._load()
        existing = data.get(key)
        records = existing if isinstance(existing, list) \
            else ([existing] if existing else [])
        sig = record.get("search")
        records = [r for r in records if r.get("search") != sig]
        records.append(record)
        data[key] = records
        self._store(data)

    def entries(self) -> Dict[str, dict]:
        return self._load()

    @staticmethod
    def _count(data: Dict[str, object]) -> int:
        return sum(len(v) if isinstance(v, list) else 1
                   for v in data.values())

    def clear(self) -> int:
        """Delete the cache file; returns how many records it held."""
        n = self._count(self._load())
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass
        return n

    def __len__(self) -> int:
        """Total records (not keys — a key holds one record per bounds)."""
        return self._count(self._load())
