"""Pieces copied from ``chip_smoke.py`` at commit 44de14b (PR 21), where
they were proved on the chip: the even-share assertion, the memory readings,
and the watch that turns any fallback event or package WARNING into a
failure.  (Its four-stateful split is the load's, models/dense_decoder.py;
its bit comparison against a host copy is replaced by reference.py's
fingerprints, which cost the window nothing.)  Copied, not imported: a later
PR may change the program, not the yardstick.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Sequence

# Events that mean a fast path quietly became a slower one, or a restore
# point was skipped.  Any of them fails the operation it happened in.
FALLBACK_EVENTS = frozenset(
    {
        "async_take.staging_downgrade",
        "native.degraded",
        "restore_latest.fallback",
        "journal.fallback",
    }
)


def assert_even_share(tree: Any, devices: Sequence[Any], what: str) -> int:
    """No device may hold more than its share of ``tree`` (+2% for leaves
    that replicate).  Returns the largest per-device byte count."""
    import jax

    per_device = {d: 0 for d in devices}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    worst = max(per_device.values())
    limit = total / len(devices) * 1.02 + (64 << 10)
    if worst > limit:
        raise AssertionError(
            f"{what}: a device holds {worst} bytes of a {total}-byte state over "
            f"{len(devices)} devices"
        )
    return worst


def hbm_stats(devices: Sequence[Any]) -> Dict[str, Any]:
    """Largest ``memory_stats()`` figures over the devices (None where the
    backend has none, as on the CPU)."""
    keys = ("bytes_limit", "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return dict.fromkeys(keys)
    return {key: max(s.get(key, 0) for s in stats) for key in keys}


class FallbackWatch:
    """Collects, while installed, every fallback event and every WARNING of
    the package's loggers.  ``drain()`` returns what came since the last
    call: the job charges it to the operation that was running."""

    def __init__(self) -> None:
        self._found: List[str] = []
        self.notices: List[str] = []
        watch = self

        class _Warnings(logging.Handler):
            def emit(self, record: logging.LogRecord) -> None:
                line = f"WARNING {record.name}: {record.getMessage()}"
                # The telemetry plane only observes: its notices (a save
                # slower than the trailing median) say nothing about the path
                # the bytes took, and a timing notice must not turn noise
                # into ``correct: false``.  They are kept and printed.
                if record.name.startswith("torchsnapshot_tpu.telemetry"):
                    watch.notices.append(line)
                else:
                    watch._found.append(line)

        self._handler = _Warnings(level=logging.WARNING)

    def _on_event(self, event: Any) -> None:
        if event.name in FALLBACK_EVENTS:
            self._found.append(f"EVENT {event.name} {event.metadata}")

    def __enter__(self) -> "FallbackWatch":
        from torchsnapshot_tpu.event_handlers import register_event_handler

        register_event_handler(self._on_event)
        logging.getLogger("torchsnapshot_tpu").addHandler(self._handler)
        return self

    def __exit__(self, *exc) -> None:
        from torchsnapshot_tpu.event_handlers import unregister_event_handler

        logging.getLogger("torchsnapshot_tpu").removeHandler(self._handler)
        unregister_event_handler(self._on_event)

    def drain(self) -> List[str]:
        found, self._found = self._found, []
        return found
