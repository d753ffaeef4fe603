"""Crash-surviving flight recorder: a bounded per-process event ring.

Every other telemetry plane in this repo publishes at operation *end*
(sidecars, history, traces) or ages out (the fleet spool) — a ``kill -9``
mid-take leaves nothing but filesystem debris.  This module is the
black box: a bounded ring of the most recent events, phase transitions,
lease/barrier state changes, and progress snapshots, spilled *as they
happen* to an append-only slotted file under
``$TPUSNAP_BLACKBOX/<host>-<pid>.ring`` (convention:
``<root>/telemetry/blackbox``).

Design constraints, in order:

- **Survive any death.**  Each record is ONE ``os.pwrite`` of exactly
  ``TPUSNAP_BLACKBOX_SLOT_BYTES`` bytes at a seq-derived offset.  Once the
  syscall returns, the bytes are in the page cache and survive
  ``os._exit`` / SIGKILL (only a *host* crash can lose them — there is
  deliberately no fsync on the hot path).  A reader drops at most the one
  slot torn mid-write.
- **Bounded.**  ``TPUSNAP_BLACKBOX_SLOTS`` slots, overwritten in place
  modulo the ring size: the file never grows past ``slots x slot_bytes``
  (256 KiB at defaults) no matter how long the process lives.
- **Cheap.**  One JSON encode + one pwrite per record, no locks shared
  with the pipeline, every entry point swallows its own exceptions.
  ``calibrated_overhead_s`` measures the real per-record cost the same
  way the fleet spool calibrates its publish cost.

Record format: each slot is a newline-terminated, space-padded JSON
object ``{"seq", "t" (wall clock), "host", "pid", "kind", "name",
"data"?}``.  Because every slot ends in a newline and the JSON itself
contains none, a reader needs no geometry: split on newlines, parse each
line, drop what doesn't parse (the torn slot), sort by ``seq``.

Feeds (installed by :func:`maybe_install`, called from the monitor's
``op_started``): the ``log_event`` fan-out (watchdog stalls, preemption
flush, store sweeps, journal/restore fallbacks, retries — anything any
subsystem emits), a ``phase_stats`` observer hook (phase *transitions*,
not every payload), and direct :func:`record` calls from the monitor
(op start/end, periodic progress), dist_store (lease acquire/release,
dead-peer verdicts), store.py (writer/sweep lease lifecycle), and
faults.py (the injected-crash record written immediately before
``os._exit`` — the chaos suites' ground truth).
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional

from .. import event_handlers, knobs, phase_stats
from . import metrics as tmetrics

_HOST = socket.gethostname()


class Ring:
    """One slotted ring file.  The module-level singleton wraps one for
    the live process; :func:`calibrated_overhead_s` and tests build their
    own against scratch directories."""

    def __init__(
        self,
        directory: str,
        slots: Optional[int] = None,
        slot_bytes: Optional[int] = None,
    ) -> None:
        self.directory = directory
        self.slots = slots or knobs.get_blackbox_slots()
        self.slot_bytes = slot_bytes or knobs.get_blackbox_slot_bytes()
        self.pid = os.getpid()
        self.path = os.path.join(directory, f"{_HOST}-{self.pid}.ring")
        os.makedirs(directory, exist_ok=True)
        # O_TRUNC: a pre-existing file here is a dead process's ring whose
        # pid the kernel recycled — this process's story starts empty.
        self._fd = os.open(
            self.path, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644
        )
        self._lock = threading.Lock()
        self._seq = 0
        self.records_written = 0

    def _encode(
        self, seq: int, kind: str, name: str, data: Optional[Dict[str, Any]]
    ) -> Optional[bytes]:
        rec: Dict[str, Any] = {
            "seq": seq,
            "t": time.time(),
            "host": _HOST,
            "pid": self.pid,
            "kind": kind,
            "name": str(name),
        }
        if data:
            rec["data"] = data
        buf = json.dumps(rec, separators=(",", ":"), default=str).encode(
            "utf-8", "replace"
        )
        if len(buf) >= self.slot_bytes:
            # Oversized payload: keep the envelope (that the event happened,
            # when, and in which process is the forensic signal), drop the
            # detail.
            rec.pop("data", None)
            rec["name"] = str(name)[:80]
            rec["trunc"] = True
            buf = json.dumps(rec, separators=(",", ":")).encode(
                "utf-8", "replace"
            )
            if len(buf) >= self.slot_bytes:
                return None
        return buf + b" " * (self.slot_bytes - 1 - len(buf)) + b"\n"

    def record(
        self, kind: str, name: str, data: Optional[Dict[str, Any]] = None
    ) -> bool:
        """Spill one record.  Returns False (never raises) on failure."""
        try:
            with self._lock:
                seq = self._seq
                self._seq += 1
                buf = self._encode(seq, kind, name, data)
                if buf is None:
                    return False
                os.pwrite(self._fd, buf, (seq % self.slots) * self.slot_bytes)
                self.records_written += 1
            return True
        except Exception:
            _note_spill_error()
            return False

    def close(self) -> None:
        try:
            os.close(self._fd)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Process-global recorder

_LOCK = threading.Lock()
_RING: Optional[Ring] = None
_INSTALLED = False
_SPILL_ERROR_NOTED = False
# Reentrancy guard: the event handler must not loop if recording itself
# emits an event (it doesn't today; the guard makes that a non-incident).
_IN_FEED = threading.local()


def enabled() -> bool:
    """Whether the recorder spills (``TPUSNAP_BLACKBOX`` set)."""
    return knobs.get_blackbox_dir() is not None


def _live_ring() -> Optional[Ring]:
    """The ring for the current (dir, pid) — reopened after a fork or a
    knob change, closed (to None) when the knob is unset."""
    global _RING
    directory = knobs.get_blackbox_dir()
    with _LOCK:
        if directory is None:
            if _RING is not None:
                _RING.close()
                _RING = None
            return None
        if (
            _RING is None
            or _RING.directory != directory
            or _RING.pid != os.getpid()
        ):
            if _RING is not None and _RING.pid == os.getpid():
                _RING.close()
            try:
                _RING = Ring(directory)
            except Exception:
                _note_spill_error()
                return None
        return _RING


def record(
    kind: str, name: str, data: Optional[Dict[str, Any]] = None
) -> bool:
    """Spill one record to this process's ring.  No-op (False) when the
    recorder is disabled; never raises."""
    try:
        ring = _live_ring()
    except Exception:
        return False
    if ring is None:
        return False
    ok = ring.record(kind, name, data)
    if ok:
        tmetrics.record_blackbox_record()
    return ok


def ring_path() -> Optional[str]:
    """Path of this process's live ring file, or None when disabled."""
    ring = _live_ring()
    return ring.path if ring is not None else None


def records_written() -> int:
    """Records this process has spilled to its live ring (0 if none)."""
    with _LOCK:
        return _RING.records_written if _RING is not None else 0


def _note_spill_error() -> None:
    """Count a failed spill; surface the FIRST one per process on the
    normal event fan-out (the recorder failing silently forever would be
    an observability hole in the observability layer)."""
    global _SPILL_ERROR_NOTED
    tmetrics.record_blackbox_spill_error()
    if not _SPILL_ERROR_NOTED:
        _SPILL_ERROR_NOTED = True
        try:
            from ..event import Event

            event_handlers.log_event(
                Event(name="blackbox.spill_error", metadata={"pid": os.getpid()})
            )
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Feeds

_LAST_OBS_PHASE: Optional[str] = None


def _on_event(event: Any) -> None:
    if getattr(_IN_FEED, "active", False):
        return
    _IN_FEED.active = True
    try:
        name = getattr(event, "name", None)
        if not name:
            return
        meta = getattr(event, "metadata", None)
        data = dict(meta) if isinstance(meta, dict) else None
        record("event", name, data)
    except Exception:
        pass
    finally:
        _IN_FEED.active = False


def _on_phase(phase: str, begin: float, end: float, nbytes: int) -> None:
    # Record phase *transitions*, not every payload: per-payload volume
    # would churn the whole ring through one big phase and evict the
    # op/lease records postmortem actually needs.
    global _LAST_OBS_PHASE
    if phase == _LAST_OBS_PHASE:
        return
    _LAST_OBS_PHASE = phase
    record("phase", phase, {"dur_s": round(end - begin, 6), "nbytes": nbytes})


def maybe_install() -> None:
    """Install the recorder's passive feeds (event fan-out + phase
    observer) once per process.  Idempotent and cheap; safe to call even
    when the recorder is disabled — the feeds no-op until the knob is
    set."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        _INSTALLED = True
    event_handlers.register_event_handler(_on_event)
    phase_stats.set_observer_hook(_on_phase)


# ---------------------------------------------------------------------------
# Reader (postmortem side)


def read_ring(path: str) -> List[Dict[str, Any]]:
    """Parse one ring file into records sorted by seq.  Torn or garbage
    slots are silently dropped — that is the format's crash contract."""
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return []
    records: List[Dict[str, Any]] = []
    for line in raw.split(b"\n"):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "seq" in rec and "kind" in rec:
            records.append(rec)
    records.sort(key=lambda r: r.get("seq", 0))
    return records


def read_all(directory: str) -> Dict[str, List[Dict[str, Any]]]:
    """All rings under a blackbox directory: ``{path: records}``."""
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return {}
    return {
        os.path.join(directory, n): read_ring(os.path.join(directory, n))
        for n in names
        if n.endswith(".ring")
    }


# ---------------------------------------------------------------------------
# Calibration


def calibrated_overhead_s(samples: int = 200) -> Dict[str, float]:
    """Measured per-record cost against a scratch ring, scaled by this
    process's actual record count — the same estimate-by-parts shape as
    the fleet spool's and tracer's calibration (a live in-band timing
    would itself be the overhead it measures)."""
    import shutil
    import tempfile

    scratch = tempfile.mkdtemp(prefix="tpusnap-blackbox-cal-")
    try:
        ring = Ring(scratch)
        payload = {"op_id": "calibration", "rank": 0, "bytes": 123456789}
        begin = time.perf_counter()
        for i in range(samples):
            ring.record("event", "calibration.sample", payload)
        elapsed = time.perf_counter() - begin
        ring.close()
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    per_record = elapsed / max(1, samples)
    n = records_written()
    return {
        "per_record_s": per_record,
        "records": float(n),
        "estimated_s": per_record * n,
    }
