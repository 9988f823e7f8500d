"""Compile-time kernel autotuning with a persisted on-disk cache.

The Pallas backend ran one static ``block_rows = 512`` kernel row grid and
one static ``block_size = 4096`` scan block for every relation shape; LMFAO's
bottom layers win precisely by specializing this kind of low-level choice to
the workload.  This module times candidate ``(block_size, block_rows)``
pairs on synthetic data matching a step's *signature* — relation row count,
segment-layout width, payload width, node-axis N, backend, host platform —
and memoizes the winner.

Keying follows the PR-5 runner-cache convention (a tuple of exactly the
inputs that determine the compiled program); signatures bucket the continuous
dimensions (row count, widths) to the next power of two so one tuning run
serves a whole neighborhood of shapes instead of re-timing per relation.

The cache persists as JSON (``REPRO_AUTOTUNE_CACHE`` env, default
``~/.cache/repro/autotune.json``) so *warm sessions never re-tune*: a second
process with the same signatures does zero timing runs (``n_timed`` stays 0 —
counter-asserted in tests).  Corrupt files load as empty (re-tune); corrupt
or stale *entries* fall back to the static defaults instead of raising — a
bad cache must never take down a session (DESIGN.md §10).

Entry points: :class:`Autotuner` (owned by ``ExecutablePlan`` when the
config carries ``block_size="auto"`` / ``block_rows="auto"``) and
:func:`signature_for_step` (the bucketing rule).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, Optional, Tuple

from repro.obs.trace import span

DEFAULT_BLOCK_SIZE = 4096
DEFAULT_BLOCK_ROWS = 512
#: candidate grids — block_rows stays lane aligned (multiples of 128)
BLOCK_SIZE_CANDIDATES = (1024, 4096, 16384)
BLOCK_ROWS_CANDIDATES = (128, 256, 512, 1024)
#: timing probes cap the row axis: above this the per-row cost is flat
MAX_PROBE_ROWS = 16384
CACHE_VERSION = 2   # v2: delta-scan signatures (|update|-bucketed IVM shapes)


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def _pow2_bucket(n: int) -> int:
    b = 1
    while b < max(int(n), 1):
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class TuneSignature:
    """What a tuned choice is keyed on — the step facts that change the
    optimal blocking.  Continuous dims are pow2-bucketed."""

    backend: str        # lowering backend ("xla" | "pallas")
    platform: str       # jax.default_backend(): "cpu" | "tpu" | "gpu"
    interpret: bool     # Pallas interpret mode (CPU) times very differently
    n_rows: int         # pow2 bucket of the scanned relation's row count
    n_segments: int     # pow2 bucket of the widest segment layout in the step
    payload_width: int  # pow2 bucket of the step's total payload columns
    n_nodes: int        # param-batch (node) axis size (1 when unbatched)
    delta: bool = False  # IVM delta scan: n_rows is the |update| pad bucket

    def key(self) -> str:
        return (f"v{CACHE_VERSION}/{self.backend}/{self.platform}/"
                f"i{int(self.interpret)}/r{self.n_rows}/s{self.n_segments}/"
                f"w{self.payload_width}/n{self.n_nodes}/d{int(self.delta)}")


@dataclasses.dataclass(frozen=True)
class TuneResult:
    block_size: int
    block_rows: int
    from_cache: bool
    fallback: bool = False   # True when a corrupt entry forced the defaults


def signature_for_step(backend: str, platform: str, interpret: bool,
                       n_rows: int, n_segments: int, payload_width: int,
                       n_nodes: Optional[int], delta: bool = False) -> TuneSignature:
    """``delta=True`` marks an IVM delta scan: ``n_rows`` is then the
    |update| pad bucket, tiny relative to full-relation scans, and the
    optimal blocking differs enough to deserve its own cache lane."""
    return TuneSignature(
        backend=backend, platform=platform, interpret=bool(interpret),
        n_rows=_pow2_bucket(n_rows), n_segments=_pow2_bucket(n_segments),
        payload_width=_pow2_bucket(payload_width),
        n_nodes=_pow2_bucket(n_nodes or 1), delta=bool(delta))


def _valid_entry(e) -> bool:
    if not isinstance(e, dict):
        return False
    bs, br = e.get("block_size"), e.get("block_rows")
    if not isinstance(bs, int) or isinstance(bs, bool) or bs < 1:
        return False
    if not isinstance(br, int) or isinstance(br, bool) or br < 128 or br % 128:
        return False
    return True


class Autotuner:
    """Times candidates per signature; memoizes in memory and on disk.

    ``n_timed`` counts individual timing runs (0 across a warm session),
    ``n_hits``/``n_misses`` count cache lookups, ``n_fallbacks`` counts
    corrupt entries that degraded to the static defaults."""

    def __init__(self, cache_path: Optional[str] = None):
        self.cache_path = cache_path or default_cache_path()
        self.n_timed = 0
        self.n_hits = 0
        self.n_misses = 0
        self.n_fallbacks = 0
        self._entries: Dict[str, dict] = self._load()

    # -- persistence ---------------------------------------------------------

    def _load(self) -> Dict[str, dict]:
        try:
            with open(self.cache_path) as f:
                blob = json.load(f)
        except (OSError, ValueError):
            return {}     # missing or corrupt file: start empty, re-tune
        if not isinstance(blob, dict) or blob.get("version") != CACHE_VERSION:
            return {}     # stale format: discard wholesale
        entries = blob.get("entries")
        return dict(entries) if isinstance(entries, dict) else {}

    def _save(self) -> None:
        path = self.cache_path
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"version": CACHE_VERSION, "entries": self._entries},
                          f, indent=1, sort_keys=True)
            os.replace(tmp, path)    # atomic: concurrent readers never see a
        except OSError:              # torn file
            pass                     # read-only FS etc.: cache stays in-memory

    # -- tuning --------------------------------------------------------------

    def tune(self, sig: TuneSignature) -> TuneResult:
        """The tuned ``(block_size, block_rows)`` for a signature — from the
        in-memory/on-disk cache when present (zero timing runs), otherwise
        timed now and persisted."""
        key = sig.key()
        entry = self._entries.get(key)
        if entry is not None:
            if _valid_entry(entry):
                self.n_hits += 1
                return TuneResult(entry["block_size"], entry["block_rows"],
                                  from_cache=True)
            # corrupt entry: degrade to defaults, never raise mid-compile
            self.n_fallbacks += 1
            return TuneResult(DEFAULT_BLOCK_SIZE, DEFAULT_BLOCK_ROWS,
                              from_cache=False, fallback=True)
        self.n_misses += 1
        with span("autotune.tune", key=key):
            block_size, block_rows = self._time_candidates(sig)
        self._entries[key] = {"block_size": int(block_size),
                              "block_rows": int(block_rows),
                              "sig": dataclasses.asdict(sig)}
        self._save()
        return TuneResult(int(block_size), int(block_rows), from_cache=False)

    # -- timing probes -------------------------------------------------------

    def _probe_rows(self, sig: TuneSignature) -> int:
        return min(sig.n_rows, MAX_PROBE_ROWS)

    def _time(self, fn) -> float:
        """Median-of-3 wall seconds after one warmup (compile) run.

        The only telemetry site allowed to sync the device: probes run at
        bind time, outside any trace and outside the steady-state contract
        (their whole purpose is wall timing)."""
        import jax
        with span("autotune.probe"):
            jax.block_until_ready(fn())
            self.n_timed += 1
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                times.append(time.perf_counter() - t0)
        times.sort()
        return times[1]

    def _time_candidates(self, sig: TuneSignature) -> Tuple[int, int]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        rng = np.random.default_rng(0)
        n = self._probe_rows(sig)
        n_seg = max(sig.n_segments, 1)
        width = max(sig.payload_width, 1)
        seg = jnp.asarray(rng.integers(0, n_seg, n).astype(np.int32))
        pay = jnp.asarray(rng.normal(size=(n, width)).astype(np.float32))

        # block_rows: the kernel row grid (pallas only — the xla backend has
        # no kernel grid, so it keeps the default)
        block_rows = DEFAULT_BLOCK_ROWS
        if sig.backend == "pallas":
            from repro.kernels import ops
            best = None
            for cand in BLOCK_ROWS_CANDIDATES:
                t = self._time(lambda: ops.seg_aggregate(
                    seg, pay, n_seg, block_rows=cand,
                    interpret=sig.interpret))
                if best is None or t < best[0]:
                    best = (t, cand)
            block_rows = best[1]

        # block_size: the outer lax.scan row block (both backends) — probe a
        # blocked segment-sum scan shaped like one step
        best = None
        for cand in BLOCK_SIZE_CANDIDATES:
            B = min(cand, n)
            n_blocks = max(n // B, 1)
            segs = seg[:n_blocks * B].reshape(n_blocks, B)
            pays = pay[:n_blocks * B].reshape(n_blocks, B, width)

            def probe(segs=segs, pays=pays):
                def body(acc, xs):
                    s, p = xs
                    return acc + jax.ops.segment_sum(
                        p, s, num_segments=n_seg), None
                acc = jnp.zeros((n_seg, width), jnp.float32)
                return jax.lax.scan(body, acc, (segs, pays))[0]

            t = self._time(jax.jit(probe))
            if best is None or t < best[0]:
                best = (t, cand)
        return best[1], block_rows
