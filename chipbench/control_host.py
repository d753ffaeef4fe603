"""``control.py`` for a cell whose state leaves the device no room for the
control's copy: the same control, its lower-precision copy kept on the host.

    python3 chipbench/control_host.py --workload <cell> --seeds 11,12,13 --seconds 5

``reference.LowerPrecisionStore`` keeps the state one precision down ON THE
DEVICE, beside the live state and through the set-up's train step.  At
``lfm2-8b-a1b.kill-resume`` that is 9.39 GB of state + 3.14 GB of the step's
temporaries + 1.08 GB of program + 4.69 GB of float8 copy: over one chip's
16.9 GB.  Where the copy is kept is no part of what the control shows (the
reference in the library's place, one precision below what the configuration
states, handed back in the stated dtype), so this one lowers each leaf on
the device as the other does, keeps the result in host memory, and copies it
back to the device leaf by leaf at the restore.  Same arguments, same
output lines, same exit code as ``control.py``, whose loop it runs.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference  # noqa: E402


OnTheDevice = reference.LowerPrecisionStore  # by this name: ``main`` rebinds the other


class HostKeptLowerPrecisionStore(OnTheDevice):
    """Each leaf lowered on the device as the other store lowers it, then
    kept as a host array; ``restore_latest`` is the other store's, which
    casts what was kept back to the stated dtype and copies it to the
    device."""

    @staticmethod
    def _lower(x):
        import numpy as np

        return np.asarray(OnTheDevice._lower(x))


def main() -> int:
    from chipbench import control

    reference.LowerPrecisionStore = HostKeptLowerPrecisionStore
    try:
        return control.main()
    finally:
        reference.LowerPrecisionStore = OnTheDevice


if __name__ == "__main__":
    sys.exit(main())
