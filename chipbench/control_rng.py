"""``control_host.py`` for a cell whose app state holds an ``RNGState``: the
same control, with the RNG state passed through as it is.

    python3 chipbench/control_rng.py --workload sdar30b.kill-resume --seeds 11,12 --seconds 5

``reference.LowerPrecisionStore`` maps its lowering over every leaf of every
stateful's ``state_dict()``; an ``RNGState``'s holds a string and Python ints
(the process's generators) beside the key's data, which have no lower
precision.  This store keeps that stateful's ``state_dict()`` as it is and
loads it back as it is, and lowers every other stateful one precision down,
as ``control_host.py`` does, on the device and kept on the host.  Same
arguments, same output lines, same exit code as ``control.py``, whose loop
it runs.
"""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import control_host, reference  # noqa: E402


class RNGPassingStore(control_host.HostKeptLowerPrecisionStore):
    """The host-kept control, an ``RNGState`` kept and handed back whole."""

    @staticmethod
    def _rng_keys(app_state):
        from torchsnapshot_tpu import RNGState

        return [key for key, stateful in app_state.items() if isinstance(stateful, RNGState)]

    def save(self, step, app_state):
        rng = self._rng_keys(app_state)
        super().save(step, {k: v for k, v in app_state.items() if k not in rng})
        self._kept[step].update({k: copy.deepcopy(app_state[k].state_dict()) for k in rng})

    def restore_latest(self, app_state):
        rng = self._rng_keys(app_state)
        step = super().restore_latest({k: v for k, v in app_state.items() if k not in rng})
        if step is not None:
            for k in rng:
                app_state[k].load_state_dict(copy.deepcopy(self._kept[step][k]))
        return step


def main() -> int:
    from chipbench import control

    reference.LowerPrecisionStore = RNGPassingStore
    try:
        return control.main()
    finally:
        reference.LowerPrecisionStore = control_host.OnTheDevice


if __name__ == "__main__":
    sys.exit(main())
