"""Peer-to-peer chunk distribution: the cross-host serving tier.

The host cache (cache.py) got N co-located workers down to ONE origin read
per chunk per host; at fleet scale the origin is still re-read once per
host.  This module adds the missing hop: hosts that already hold a chunk
serve it to hosts that don't, so each chunk leaves the origin once per
*fleet* — torrent-style, but with none of the protocol surface, because
every ingredient already exists in the repo:

- **Identity** — chunks are digest-addressed (``cas://`` / ``casx://``
  parts, cache keys ``cas/<algo>/<hex>``).  A peer's bytes are verified
  against the NAME that requested them before anything trusts them, so a
  corrupt or malicious peer can waste a round-trip but never corrupt a
  restore.
- **Discovery** — daemons (peerd.py) register on the same ``dist_store``
  KV plane multi-rank saves already coordinate through, with the op-lease
  stamp/tombstone/grace rules from the liveness machinery: a daemon that
  stops refreshing its stamp past the grace window silently drops out of
  the candidate set.  No new protocol, no membership service.
- **Placement** — the fetch policy rendezvous-hashes each digest over the
  live peer set, so a fleet's requests for one chunk converge on the same
  few holders (high hit odds) while distinct chunks spread over all peers
  (no hot spot).
- **Transport** — plain HTTP/1.1 range requests against peerd
  (``GET /chunk/<algo>/<digest>``); stdlib only on both ends, and the wire
  format is consumable by anything that can speak HTTP (see
  examples/http_range_pull.py).

:class:`PeerReaderPlugin` layers OUTSIDE :class:`cache.CacheReaderPlugin`:
a read that the local cache can serve never touches the network; a miss is
resolved peer-first (verify-by-digest on receipt, bounded transient retry,
bad-peer quarantine) and lands in the local cache, so the inner cache read
that follows is a hit — and this host can in turn serve the chunk onward.
Only a peer miss falls through to origin, which keeps the cache layer's
``miss_bytes`` an exact origin-bytes meter.  ``casx://`` locations are
fetched at sub-chunk granularity: each part rendezvous-routes to its own
peer, so a large payload's parts stream from several hosts concurrently.

Failure is never load-bearing: no store, no live peers, a dead peer mid-
transfer, a full cache disk — every path degrades to the plain
cache-then-origin read the repo already trusts.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .io_types import ReadIO, StoragePlugin, WriteIO

logger = logging.getLogger(__name__)

__all__ = [
    "PEERD_PREFIX",
    "PeerInfo",
    "PeerRegistration",
    "live_peers",
    "rendezvous_order",
    "PeerClient",
    "PeerReaderPlugin",
    "maybe_wrap_peer_reads",
    "find_peer_reader",
    "reader_stats",
    "process_stats",
    "reset_process_stats",
    "peer_scoreboard",
    "reset_peer_scoreboard",
    "record_fetch_outcome",
    "calibrated_scoreboard_cost_s",
]

# ------------------------------------------------------------ process stats

_TOTALS_LOCK = threading.Lock()
_TOTALS: Dict[str, int] = {
    "hits": 0,
    "misses": 0,
    "hit_bytes": 0,
    "miss_bytes": 0,
    "rejects": 0,
}


def process_stats() -> Dict[str, int]:
    """Cumulative peer-tier counters folded in by closed plugins — the
    fleet-telemetry row (telemetry/fleet.py), mirroring cache.py's."""
    with _TOTALS_LOCK:
        return dict(_TOTALS)


def reset_process_stats() -> None:
    with _TOTALS_LOCK:
        for k in _TOTALS:
            _TOTALS[k] = 0
    reset_peer_scoreboard()


def _add_totals(**deltas: int) -> None:
    with _TOTALS_LOCK:
        for k, v in deltas.items():
            _TOTALS[k] = _TOTALS.get(k, 0) + v


# ------------------------------------------------------------- scoreboard
#
# Per-peer serving health, fed by the same timings the peer_fetch spans
# record: latency and error EWMAs, a bounded latency ring for percentiles,
# byte/outcome counters, and the quarantine stamp.  Published through the
# fleet spool (telemetry/fleet.py folds it into the PEERS table) and fed
# BACK into fetch policy: a peer whose latency EWMA exceeds
# TPUSNAP_PEER_DEMOTE_FACTOR x the fleet median (or whose error EWMA
# crosses 0.5) is demoted — moved to the back of the rendezvous order, so
# it stops dominating tail latency without being unreachable.

_SCORE_LOCK = threading.Lock()
_SCORE_ALPHA = 0.2
_SCORE_RING = 128
_SCOREBOARD: Dict[str, Dict[str, Any]] = {}
_SCORE_UPDATES = 0

_OUTCOME_COUNTER = {
    "hit": "hits",
    "miss": "misses",
    "error": "errors",
    "reject": "rejects",
}


def _score_entry_locked(addr: str) -> Dict[str, Any]:
    entry = _SCOREBOARD.get(addr)
    if entry is None:
        entry = {
            "ewma_latency_s": 0.0,
            "ewma_error": 0.0,
            "latencies": [],
            "hits": 0,
            "misses": 0,
            "errors": 0,
            "rejects": 0,
            "bytes": 0,
            "quarantined_until": 0.0,
            "demoted": False,
        }
        _SCOREBOARD[addr] = entry
    return entry


def record_fetch_outcome(
    addr: str, wall_s: float, status: str, nbytes: int = 0
) -> bool:
    """Fold one fetch's outcome into the peer's scoreboard row.  Returns
    True when this update newly demoted the peer (the caller owns the
    event/metric emission — never under the lock)."""
    global _SCORE_UPDATES
    from . import knobs

    factor = knobs.get_peer_demote_factor()
    with _SCORE_LOCK:
        _SCORE_UPDATES += 1
        entry = _score_entry_locked(addr)
        total = (
            entry["hits"] + entry["misses"] + entry["errors"] + entry["rejects"]
        )
        if total == 0:
            entry["ewma_latency_s"] = wall_s
        else:
            entry["ewma_latency_s"] = (
                (1.0 - _SCORE_ALPHA) * entry["ewma_latency_s"]
                + _SCORE_ALPHA * wall_s
            )
        err = 0.0 if status in ("hit", "miss") else 1.0
        entry["ewma_error"] = (
            (1.0 - _SCORE_ALPHA) * entry["ewma_error"] + _SCORE_ALPHA * err
        )
        entry["latencies"].append(wall_s)
        if len(entry["latencies"]) > _SCORE_RING:
            del entry["latencies"][: len(entry["latencies"]) - _SCORE_RING]
        entry[_OUTCOME_COUNTER.get(status, "errors")] += 1
        entry["bytes"] += nbytes
        was_demoted = entry["demoted"]
        # Demotion is relative health: compare against the fleet median of
        # latency EWMAs so one uniformly slow network never demotes anyone.
        ewmas = sorted(
            e["ewma_latency_s"]
            for e in _SCOREBOARD.values()
            if e["hits"] + e["misses"] + e["errors"] + e["rejects"] > 0
        )
        median = ewmas[len(ewmas) // 2] if ewmas else 0.0
        slow = (
            factor > 0.0
            and len(ewmas) >= 2
            and median > 0.0
            and entry["ewma_latency_s"] > factor * median
        )
        flaky = entry["ewma_error"] > 0.5
        entry["demoted"] = slow or flaky
        return entry["demoted"] and not was_demoted


def record_quarantine(addr: str, ttl_s: float) -> None:
    with _SCORE_LOCK:
        entry = _score_entry_locked(addr)
        entry["quarantined_until"] = max(
            entry["quarantined_until"], time.time() + ttl_s
        )


def _demoted_addrs() -> set:
    with _SCORE_LOCK:
        return {a for a, e in _SCOREBOARD.items() if e["demoted"]}


def _percentile_locked(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))]


def peer_scoreboard() -> Dict[str, Dict[str, Any]]:
    """Snapshot for publication: per-peer EWMAs, ring percentiles, and
    counters (the raw latency ring stays private — bounded spool docs)."""
    with _SCORE_LOCK:
        out: Dict[str, Dict[str, Any]] = {}
        for addr, entry in _SCOREBOARD.items():
            lats = sorted(entry["latencies"])
            out[addr] = {
                "ewma_latency_s": entry["ewma_latency_s"],
                "ewma_error": entry["ewma_error"],
                "p50_s": _percentile_locked(lats, 0.50),
                "p99_s": _percentile_locked(lats, 0.99),
                "hits": entry["hits"],
                "misses": entry["misses"],
                "errors": entry["errors"],
                "rejects": entry["rejects"],
                "bytes": entry["bytes"],
                "quarantined_until": entry["quarantined_until"],
                "demoted": entry["demoted"],
            }
        return out


def reset_peer_scoreboard() -> None:
    global _SCORE_UPDATES
    with _SCORE_LOCK:
        _SCOREBOARD.clear()
        _SCORE_UPDATES = 0


def calibrated_scoreboard_cost_s(samples: int = 200) -> Dict[str, Any]:
    """Isolated per-update scoreboard cost x updates this process — the
    scoreboard half of a serving worker's overhead bill (same shape as
    trace.calibrated_span_cost_s / fleet.calibrated_overhead_s)."""
    global _SCORE_UPDATES
    updates = _SCORE_UPDATES
    probe_addr = "calibration.invalid:0"
    t0 = time.perf_counter()
    for _ in range(max(1, samples)):
        record_fetch_outcome(probe_addr, 0.001, "hit", 1)
    per_update = (time.perf_counter() - t0) / max(1, samples)
    with _SCORE_LOCK:
        _SCOREBOARD.pop(probe_addr, None)
        _SCORE_UPDATES = max(0, _SCORE_UPDATES - max(1, samples))
    return {
        "per_update_s": per_update,
        "updates": updates,
        "estimated_s": per_update * updates,
    }


# ------------------------------------------------------------ the registry
#
# Daemons register under one KV prefix with exactly the op-lease lifecycle
# (dist_store.OpLease): a monotonically-assigned slot, a wall-clock stamp
# refreshed every lease interval, a tombstone on clean shutdown, and the
# grace-window presumed-dead rule on the read side.  Readers scan the slot
# range — bounded by the fleet's total daemon launches, the same shape the
# lease table already has.

PEERD_PREFIX = "peerd"
_SLOTS_KEY = PEERD_PREFIX + "/slots"


class PeerInfo:
    """One live daemon from the registry."""

    __slots__ = ("slot", "addr", "host", "pid", "stamp")

    def __init__(
        self, slot: int, addr: str, host: str, pid: int, stamp: float
    ) -> None:
        self.slot = slot
        self.addr = addr
        self.host = host
        self.pid = pid
        self.stamp = stamp

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"PeerInfo(slot={self.slot}, addr={self.addr!r})"


class PeerRegistration:
    """This process's row in the peer registry: slot claim, stamp-refresh
    thread, tombstone on close.  The refresh thread is a daemon thread —
    a kill -9 simply stops the stamps, and the grace window retires the
    row, which is the whole point."""

    def __init__(
        self,
        store: Any,
        addr: str,
        interval_s: Optional[float] = None,
    ) -> None:
        from . import knobs

        self._store = store
        self.addr = addr
        self._interval_s = (
            interval_s if interval_s is not None else knobs.get_lease_interval_s()
        )
        self.slot = int(store.add(_SLOTS_KEY, 1)) - 1
        self._key = f"{PEERD_PREFIX}/{self.slot}"
        self._stop = threading.Event()
        self._write(done=False)
        self._thread = threading.Thread(
            target=self._run, name="tpusnap_peerd_lease", daemon=True
        )
        self._thread.start()

    def _write(self, done: bool) -> None:
        record = {
            "addr": self.addr,
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "stamp": time.time(),
            "done": done,
        }
        self._store.set(self._key, json.dumps(record).encode("utf-8"))

    def _run(self) -> None:
        while not self._stop.wait(self._interval_s):
            try:
                self._write(done=False)
            except Exception:  # noqa: BLE001 - refresh must never kill the host
                logger.warning("peer registry refresh failed", exc_info=True)

    def close(self) -> None:
        """Stop refreshing and tombstone the row (readers skip it
        immediately instead of waiting out the grace window)."""
        self._stop.set()
        self._thread.join(timeout=2.0)
        try:
            self._write(done=True)
        except Exception:  # noqa: BLE001
            logger.warning("peer registry tombstone failed", exc_info=True)


def live_peers(
    store: Any,
    grace_s: Optional[float] = None,
    exclude_addr: Optional[str] = None,
) -> List[PeerInfo]:
    """Every registered daemon whose stamp is fresher than the grace
    window — the candidate set.  Tombstoned (cleanly stopped) and stale
    (presumed dead) rows are skipped; malformed rows are ignored rather
    than fatal, because the registry is advisory."""
    from . import knobs

    if grace_s is None:
        grace_s = knobs.get_peer_grace_s()
    raw = store.try_get(_SLOTS_KEY)
    try:
        count = int(raw) if raw else 0
    except ValueError:
        count = 0
    now = time.time()
    peers: List[PeerInfo] = []
    for slot in range(count):
        blob = store.try_get(f"{PEERD_PREFIX}/{slot}")
        if blob is None:
            continue
        try:
            rec = json.loads(blob)
            addr = str(rec["addr"])
            stamp = float(rec.get("stamp", 0.0))
        except (ValueError, KeyError, TypeError):
            continue
        if rec.get("done"):
            continue
        if grace_s > 0 and now - stamp > grace_s:
            continue
        if exclude_addr is not None and addr == exclude_addr:
            continue
        peers.append(
            PeerInfo(
                slot=slot,
                addr=addr,
                host=str(rec.get("host", "")),
                pid=int(rec.get("pid", 0)),
                stamp=stamp,
            )
        )
    return peers


def rendezvous_order(chunk_key: str, peers: List[PeerInfo]) -> List[PeerInfo]:
    """Peers ranked by rendezvous (highest-random-weight) hash of
    ``(chunk, peer)``: every host computes the same ranking from the same
    membership, so a fleet's requests for one digest converge on the same
    preferred holder while distinct digests spread across all peers.
    Membership churn moves only the affected 1/N of digests."""

    def _score(peer: PeerInfo) -> bytes:
        return hashlib.sha1(
            f"{chunk_key}|{peer.addr}".encode("utf-8")
        ).digest()

    return sorted(peers, key=_score, reverse=True)


# ------------------------------------------------------------- the client


class PeerClient:
    """Digest-addressed chunk fetches against the live peer set.

    Policy per chunk: rendezvous-ranked candidates, per-peer bounded
    transient retry (retry.is_transient — connection resets and 5xx retry,
    a 404 just means "not resident there"), digest verification on every
    body before it is trusted, and a quarantine for peers that served
    corrupt bytes or exhausted their budget.  Returns None when no peer
    could serve — the caller falls back to origin.
    """

    def __init__(self, store: Any, self_addr: Optional[str] = None) -> None:
        from . import faults, knobs

        self._store = store
        self._self_addr = self_addr
        self._timeout_s = knobs.get_peer_timeout_s()
        self._retries = knobs.get_peer_retries()
        self._grace_s = knobs.get_peer_grace_s()
        self._bad_ttl_s = knobs.get_peer_bad_ttl_s()
        self._lock = threading.Lock()
        self._bad: Dict[str, float] = {}
        self.rejects = 0
        self._injector = faults.maybe_peer_injector(knobs.get_faults_spec())

    # ------------------------------------------------------- membership

    def candidates(self, chunk_key: str) -> List[PeerInfo]:
        try:
            peers = live_peers(
                self._store, grace_s=self._grace_s, exclude_addr=self._self_addr
            )
        except Exception:  # noqa: BLE001 - a broken store = no peers
            logger.warning("peer registry scan failed", exc_info=True)
            return []
        now = time.monotonic()
        with self._lock:
            healthy = [p for p in peers if self._bad.get(p.addr, 0.0) <= now]
        ranked = rendezvous_order(chunk_key, healthy)
        # Scoreboard feedback: demoted peers stay reachable (they may be
        # the only holder) but are tried last, so a persistently slow peer
        # stops setting the fleet's tail latency.
        demoted = _demoted_addrs()
        if demoted:
            ranked = [p for p in ranked if p.addr not in demoted] + [
                p for p in ranked if p.addr in demoted
            ]
        return ranked

    def mark_bad(self, addr: str) -> None:
        with self._lock:
            self._bad[addr] = time.monotonic() + self._bad_ttl_s
        record_quarantine(addr, self._bad_ttl_s)

    def _record_reject(self, addr: str, reason: str) -> None:
        from .event import Event
        from .event_handlers import log_event
        from .telemetry import metrics as tmetrics
        from .telemetry import trace as ttrace

        with self._lock:
            self.rejects += 1
        tmetrics.record_peer_reject(reason)
        metadata: Dict[str, Any] = {"peer": addr, "reason": reason}
        trace_id = ttrace.current_trace_id()
        if trace_id is not None:
            metadata["trace"] = trace_id
        log_event(Event(name="peer.reject", metadata=metadata))
        logger.warning("rejecting peer %s: %s", addr, reason)

    # ------------------------------------------------------------ fetch

    def fetch_chunk(self, algo: str, hexdigest: str) -> Optional[bytes]:
        """The chunk's verified bytes from the best live peer, or None."""
        chunk_key = f"{algo}/{hexdigest}"
        for peer in self.candidates(chunk_key):
            data = self._fetch_from(peer.addr, algo, hexdigest)
            if data is not None:
                return data
        return None

    def _fetch_from(
        self, addr: str, algo: str, hexdigest: str
    ) -> Optional[bytes]:
        from urllib import error as urlerror

        from . import integrity, retry
        from .event import Event
        from .event_handlers import log_event
        from .telemetry import metrics as tmetrics
        from .telemetry import trace as ttrace

        path = f"/chunk/{algo}/{hexdigest}"
        begin = time.monotonic()
        status = "error"
        ttfb_s = 0.0
        result: Optional[bytes] = None
        with ttrace.span(
            "peer_fetch", cat="phase", peer=addr, digest=f"{algo}:{hexdigest}"
        ) as sp:
            attempt = 0
            while True:
                try:
                    data, ttfb_s = self._http_get(addr, path)
                except urlerror.HTTPError as e:
                    if e.code == 404:
                        status = "miss"  # not resident there, not a fault
                        break
                    if (
                        e.code in retry.TRANSIENT_HTTP_STATUS
                        and attempt < self._retries
                    ):
                        attempt += 1
                        retry.sleep_backoff(attempt, base_s=0.1)
                        continue
                    self.mark_bad(addr)
                    status = "error"
                    break
                except Exception as e:  # noqa: BLE001
                    if self._transportish(e) and attempt < self._retries:
                        attempt += 1
                        retry.sleep_backoff(attempt, base_s=0.1)
                        continue
                    self.mark_bad(addr)
                    status = "error"
                    break
                expect = f"{algo}:{hexdigest}"
                if integrity.digest_as(data, expect) != expect:
                    # Unverifiable bytes are never trusted — a digest
                    # mismatch AND a missing hash backend both land here
                    # (fail closed; origin still serves the read).
                    self._record_reject(addr, "digest_mismatch")
                    self.mark_bad(addr)
                    status = "reject"
                    break
                status = "hit"
                result = data
                break
            wall_s = time.monotonic() - begin
            sp.set(
                status=status,
                attempts=attempt + 1,
                ttfb_s=ttfb_s,
                transfer_s=max(0.0, wall_s - ttfb_s),
                bytes=len(result) if result is not None else 0,
            )
        tmetrics.record_peer_fetch_seconds(wall_s)
        newly_demoted = record_fetch_outcome(
            addr, wall_s, status, len(result) if result is not None else 0
        )
        if newly_demoted:
            tmetrics.record_peer_demoted()
            metadata: Dict[str, Any] = {"peer": addr, "status": status}
            trace_id = ttrace.current_trace_id()
            if trace_id is not None:
                metadata["trace"] = trace_id
            log_event(Event(name="peer.demoted", metadata=metadata))
            logger.warning("demoting slow/flaky peer %s", addr)
        return result

    @staticmethod
    def _transportish(exc: BaseException) -> bool:
        """Transient classification widened for the HTTP client: urllib
        wraps socket errors in URLError (an OSError whose errno is often
        unset), which retry.is_transient alone would call terminal."""
        from urllib import error as urlerror

        from . import retry

        if retry.is_transient(exc):
            return True
        if isinstance(exc, (urlerror.URLError, socket.timeout)):
            return True
        return False

    def _http_get(
        self, addr: str, path: str, byte_range: Optional[Tuple[int, int]] = None
    ) -> Tuple[bytes, float]:
        """One HTTP GET against a peer.  Returns ``(body, ttfb_s)`` — the
        time-to-first-byte (connect + request + response headers) split
        from the body transfer, so the peer_fetch span can tell a slow
        network from a slow disk."""
        from urllib import request as urlrequest

        from . import phase_stats, retry
        from .telemetry import trace as ttrace

        rule = self._injector.fire(path) if self._injector is not None else None
        if rule is not None:
            if rule.kind == "peer_unreachable":
                raise ConnectionError(f"injected peer_unreachable for {path}")
            if rule.kind == "peer_slow":
                time.sleep(rule.param if rule.param is not None else 0.25)
        begin = time.monotonic()
        req = urlrequest.Request(f"http://{addr}{path}")
        traceparent = ttrace.current_traceparent()
        if traceparent is not None:
            req.add_header("traceparent", traceparent)
        if path.startswith("/chunk/"):
            req.add_header(
                "tpusnap-chunk", path[len("/chunk/"):].replace("/", ":", 1)
            )
        if byte_range is not None:
            req.add_header("Range", f"bytes={byte_range[0]}-{byte_range[1] - 1}")
        with urlrequest.urlopen(req, timeout=self._timeout_s) as resp:
            ttfb_s = time.monotonic() - begin  # headers in hand, body pending
            body = resp.read()
            clen = resp.headers.get("Content-Length")
        if rule is not None and rule.kind == "peer_truncated":
            # Simulated torn transfer: the received body is cut AFTER the
            # wire framing checks, so the digest gate is what catches it.
            body = body[: len(body) // 2]
        elif clen is not None and len(body) != int(clen):
            raise retry.StorageTransientError(
                f"truncated peer body from {addr}{path}: "
                f"{len(body)} != {clen}"
            )
        phase_stats.add("peer_read", time.monotonic() - begin, len(body))
        return body, ttfb_s


# ------------------------------------------------------------- the plugin


class PeerReaderPlugin(StoragePlugin):
    """Resolves digest-addressed cache misses peer-first.

    Sits OUTSIDE the cache reader: a read the local cache can serve is
    answered below without network; a miss on a ``cas://`` chunk (or any
    part of a ``casx://`` location) is fetched from a peer, verified, and
    POPULATED into the cache, then the read is delegated inward — so the
    inner cache serves it as a hit and the cache's miss counter keeps
    metering exactly the bytes that truly came from origin.  Non-digest
    paths (protocol files, fingerprint-namespaced objects) pass straight
    through: only content that can be verified by name may cross hosts.

    Ranged reads delegate inward untouched: a partial body cannot be
    verified against the whole-chunk digest, and ``warm``/restore issue
    whole-object reads anyway.
    """

    def __init__(
        self,
        inner: StoragePlugin,
        store: Any,
        namespace: str,
        client: PeerClient,
    ) -> None:
        from concurrent.futures import ThreadPoolExecutor

        self._inner = inner
        self._store = store
        self._ns = namespace
        self._client = client
        self.supports_scatter = getattr(inner, "supports_scatter", False)
        self.supports_write_hash = getattr(inner, "supports_write_hash", False)
        # Own pool: peer fetches block on the network and must not occupy
        # the inner cache plugin's threads (its populate lock waiters park
        # there).
        self._executor = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="tpusnap_peer"
        )
        self._lock = threading.Lock()
        self._inflight: Dict[str, threading.Lock] = {}
        self.hits = 0
        self.misses = 0
        self.hit_bytes = 0
        self.miss_bytes = 0
        self._closed = False

    def _get_executor(self):
        return self._executor

    def _record_hit(self, nbytes: int) -> None:
        with self._lock:
            self.hits += 1
            self.hit_bytes += nbytes

    def _record_miss(self, nbytes: int) -> None:
        with self._lock:
            self.misses += 1
            self.miss_bytes += nbytes

    # ------------------------------------------------------------- reads

    async def read(self, read_io: ReadIO) -> None:
        from . import cas

        try:
            if cas.is_cas_location(read_io.path):
                # Ranged or whole: ensure the FULL chunk resident (a peer
                # body is only verifiable whole) and let the cache tier
                # slice the requested range out of the resident object.
                await self._read_cas(read_io)
                return
            if cas.is_casx_location(read_io.path):
                if read_io.byte_range is None:
                    await self._read_casx(read_io)
                else:
                    await self._read_casx_range(read_io)
                return
        except Exception:  # noqa: BLE001 - peer tier is never load-bearing
            logger.warning(
                "peer-first read failed for %s; origin fallback",
                read_io.path,
                exc_info=True,
            )
        await self._inner.read(read_io)

    def _ensure_chunk(self, algo: str, hexdigest: str) -> Optional[int]:
        """Make ``cas/<algo>/<hex>`` cache-resident via a peer if it isn't
        already.  Returns the peer-fetched byte count, 0 when already
        resident, None when no peer could serve (origin's turn).

        Single-flight per key within this process: a restore issues many
        concurrent ranged reads against the same slab chunk, and without
        the gate each would pull its own full copy from the peer."""
        key = f"cas/{algo}/{hexdigest}"
        if self._store.resident_nbytes(key) is not None:
            return 0
        with self._lock:
            gate = self._inflight.setdefault(key, threading.Lock())
        with gate:
            if self._store.resident_nbytes(key) is not None:
                return 0  # a sibling's fetch landed while we queued
            try:
                data = self._client.fetch_chunk(algo, hexdigest)
                if data is None:
                    return None
                if not self._store.put(
                    key, data, expect_digest=f"{algo}:{hexdigest}"
                ):
                    return None  # populate failed (disk?): let origin serve
                self._record_hit(len(data))
                return len(data)
            finally:
                with self._lock:
                    self._inflight.pop(key, None)

    async def _read_cas(self, read_io: ReadIO) -> None:
        import asyncio

        from . import cas

        algo, hexdigest = cas.parse_cas_location(read_io.path)
        loop = asyncio.get_running_loop()
        fetched = await loop.run_in_executor(
            self._executor, self._ensure_chunk, algo, hexdigest
        )
        await self._inner.read(read_io)
        if fetched is None:
            self._record_miss(memoryview(read_io.buf).nbytes)

    async def _read_casx(self, read_io: ReadIO) -> None:
        """Sub-chunk-granular fetch: each part of a ``casx://`` location
        rendezvous-routes to its own peer, misses fall through to origin
        PER PART (through the inner stack, so the cache populates them),
        and the payload is assembled from the now-resident parts.  The
        whole-entry cache key is deliberately NOT populated — parts are
        the shared currency (this host can serve them onward) and storing
        the assembly too would double the disk cost."""
        import asyncio

        from . import cache as cache_mod
        from . import cas

        parts = cas.parse_casx_location(read_io.path)
        exact_key, _, _ = cache_mod.keys_for(self._ns, read_io.path, None)
        loop = asyncio.get_running_loop()
        if (
            await loop.run_in_executor(
                self._executor, self._store.resident_nbytes, exact_key
            )
            is not None
        ):
            await self._inner.read(read_io)
            return

        fetches = [
            loop.run_in_executor(self._executor, self._ensure_chunk, algo, hexd)
            for algo, hexd, _ in parts
        ]
        outcomes = await asyncio.gather(*fetches)
        for (algo, hexd, nbytes), outcome in zip(parts, outcomes):
            if outcome is not None:
                continue
            # No peer had it: one origin read through the inner stack —
            # the cache wrapper verifies and populates the part key.
            sub = ReadIO(path=cas.location_for(algo, hexd))
            await self._inner.read(sub)
            self._record_miss(memoryview(sub.buf).nbytes)

        total = sum(nbytes for _, _, nbytes in parts)
        if read_io.into is not None:
            out = memoryview(read_io.into).cast("B")
            if out.nbytes != total:
                raise ValueError(
                    f"casx assembly size mismatch: into={out.nbytes} "
                    f"parts={total}"
                )
        else:
            out = memoryview(bytearray(total))

        def _assemble() -> None:
            offset = 0
            for algo, hexd, nbytes in parts:
                got = self._store.get(
                    f"cas/{algo}/{hexd}", into=out[offset : offset + nbytes]
                )
                if got is not True:
                    raise KeyError(f"cas/{algo}/{hexd} not resident")
                offset += nbytes

        await loop.run_in_executor(self._executor, _assemble)
        read_io.buf = read_io.into if read_io.into is not None else out
        read_io.hash64 = None  # consumers verify with their own pass

    async def _read_casx_range(self, read_io: ReadIO) -> None:
        """A ranged read of a ``casx://`` entry: peer-ensure only the
        parts the range overlaps, then splice the range out of them.  Any
        part no peer can serve drops the whole request to the inner stack
        (one origin ranged read) — per-part origin assembly would cost
        more round-trips than the plain fallback."""
        import asyncio

        from . import cache as cache_mod
        from . import cas

        exact_key, full_key, _ = cache_mod.keys_for(
            self._ns, read_io.path, read_io.byte_range
        )
        loop = asyncio.get_running_loop()

        def _already_served() -> bool:
            if self._store.resident_nbytes(exact_key) is not None:
                return True
            nbytes = self._store.resident_nbytes(full_key)
            return nbytes is not None and read_io.byte_range[1] <= nbytes

        if await loop.run_in_executor(self._executor, _already_served):
            await self._inner.read(read_io)
            return

        parts = cas.parse_casx_location(read_io.path)
        a, b = read_io.byte_range
        overlap = []  # (algo, hexd, slice-in-part, dest offset)
        offset = 0
        for algo, hexd, nbytes in parts:
            lo, hi = max(a, offset), min(b, offset + nbytes)
            if lo < hi:
                overlap.append((algo, hexd, lo - offset, hi - offset, lo - a))
            offset += nbytes
        if b > offset:
            raise ValueError(
                f"range {read_io.byte_range} exceeds casx extent {offset}"
            )
        outcomes = await asyncio.gather(
            *(
                loop.run_in_executor(
                    self._executor, self._ensure_chunk, algo, hexd
                )
                for algo, hexd, _, _, _ in overlap
            )
        )
        if any(outcome is None for outcome in outcomes):
            await self._inner.read(read_io)
            self._record_miss(b - a)
            return

        if read_io.into is not None:
            out = memoryview(read_io.into).cast("B")
            if out.nbytes != b - a:
                raise ValueError(
                    f"casx range size mismatch: into={out.nbytes} "
                    f"range={b - a}"
                )
        else:
            out = memoryview(bytearray(b - a))

        def _assemble() -> None:
            for algo, hexd, part_lo, part_hi, dest in overlap:
                got = self._store.get(
                    f"cas/{algo}/{hexd}",
                    into=out[dest : dest + (part_hi - part_lo)],
                    byte_range=[part_lo, part_hi],
                )
                if got is not True:
                    raise KeyError(f"cas/{algo}/{hexd} not resident")

        await loop.run_in_executor(self._executor, _assemble)
        read_io.buf = read_io.into if read_io.into is not None else out
        read_io.hash64 = None  # consumers verify with their own pass

    # ------------------------------------------------------- passthroughs

    async def write(self, write_io: WriteIO) -> None:
        await self._inner.write(write_io)

    async def exists(self, path: str) -> bool:
        return await self._inner.exists(path)

    async def list_dir(self, path: str) -> List[str]:
        return await self._inner.list_dir(path)

    async def delete(self, path: str) -> None:
        await self._inner.delete(path)

    async def delete_dir(self, path: str) -> None:
        await self._inner.delete_dir(path)

    async def copy_from_sibling(self, src_root: str, path: str) -> bool:
        return await self._inner.copy_from_sibling(src_root, path)

    async def close(self) -> None:
        self._emit_summary()
        try:
            await self._inner.close()
        finally:
            self._executor.shutdown(wait=False)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "hit_bytes": self.hit_bytes,
                "miss_bytes": self.miss_bytes,
                "rejects": self._client.rejects,
            }

    def _emit_summary(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            hits, misses = self.hits, self.misses
            hit_bytes, miss_bytes = self.hit_bytes, self.miss_bytes
            rejects = self._client.rejects
        if not (hits or misses or rejects):
            return
        from .event import Event
        from .event_handlers import log_event
        from .telemetry import metrics as tmetrics

        _add_totals(
            hits=hits,
            misses=misses,
            hit_bytes=hit_bytes,
            miss_bytes=miss_bytes,
            rejects=rejects,
        )
        tmetrics.record_peer(hits, misses, hit_bytes, miss_bytes)
        if hits:
            log_event(
                Event(
                    name="peer.hit",
                    metadata={"count": hits, "bytes": hit_bytes},
                )
            )
        if misses:
            log_event(
                Event(
                    name="peer.miss",
                    metadata={"count": misses, "bytes": miss_bytes},
                )
            )
        logger.debug(
            "peer: %d chunks (%.1f MB) from peers, %d (%.1f MB) from origin,"
            " %d rejects",
            hits,
            hit_bytes / 1e6,
            misses,
            miss_bytes / 1e6,
            rejects,
        )


# ----------------------------------------------------------------- wiring


def resolve_kv_store() -> Optional[Any]:
    """The coordination KV the peer plane runs on, or None when none is
    configured — peer serving silently disabled (it is an optimization)."""
    from . import dist_store

    try:
        return dist_store.get_or_create_store(0, 1)
    except Exception:  # noqa: BLE001
        return None


def maybe_wrap_peer_reads(
    storage: StoragePlugin, self_addr: Optional[str] = None
) -> StoragePlugin:
    """Layer the peer fetch policy over a cache-wrapped read stack when
    ``TPUSNAP_PEER_FETCH`` is on and a coordination store is reachable.
    Requires the cache wrapper below (peer-fetched chunks land there);
    without it, or without a store, the stack is returned unchanged."""
    from . import cache as cache_mod
    from . import knobs

    if not knobs.peer_fetch_enabled():
        return storage
    cache_reader = cache_mod.find_reader(storage)
    if cache_reader is None:
        return storage
    kv = resolve_kv_store()
    if kv is None:
        logger.warning(
            "TPUSNAP_PEER_FETCH set but no coordination store configured; "
            "peer fetch disabled"
        )
        return storage
    if self_addr is None:
        self_addr = knobs.get_peer_addr()
    client = PeerClient(kv, self_addr=self_addr)
    return PeerReaderPlugin(
        inner=storage,
        store=cache_reader.store,
        namespace=cache_reader.namespace,
        client=client,
    )


def find_peer_reader(storage: StoragePlugin) -> Optional[PeerReaderPlugin]:
    """The PeerReaderPlugin in a wrapped storage stack, or None."""
    seen = 0
    while storage is not None and seen < 8:
        if isinstance(storage, PeerReaderPlugin):
            return storage
        storage = getattr(storage, "_inner", None)
        seen += 1
    return None


def reader_stats(storage: StoragePlugin) -> Optional[Dict[str, int]]:
    reader = find_peer_reader(storage)
    return reader.stats() if reader is not None else None
