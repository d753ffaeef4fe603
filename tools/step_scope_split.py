"""Device seconds of a cell's train step split by the load's named scopes.

    python3 chipbench/run.py --workload sdar30b.kill-resume --seed 7 --seconds 20 --trace 1 --keep-trace /tmp/t.xplane.pb
    python3 tools/step_scope_split.py --workload sdar30b.kill-resume --trace /tmp/t.xplane.pb

Run on the chip, after the traced run has exited (one process holds the
chip).  It compiles the cell's step again (the compile cache makes that
quick), maps each instruction of the compiled program to the ``op_name`` of
its metadata, and sums the device time of the trace's ``XLA Ops`` events
that fall inside the step's module by the first of ``--scopes`` that the
``op_name`` names, as a path component or wrapped by a transformation
(``jvp(head)``, ``transpose(jvp(router))``; a scope nested in another, such
as ``router`` inside ``routed_experts``, is listed first); what none names is ``other`` (the
optimizer's update, the embedding's gather, the norms between the scopes).
Prints one JSON line: seconds a step by scope, the steps seen, and the share
of the step's device time each scope takes.  No metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?metadata=\{[^}]*op_name=\"([^\"]*)\"")
_EVENT = re.compile(r"^%(\S+) = ")


def op_names(hlo_text: str) -> dict:
    names = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return names


def split(planes, names: dict, scopes, module_word: str) -> dict:
    seconds = {scope: 0.0 for scope in list(scopes) + ["other", "unnamed"]}
    steps = 0
    for plane in planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in lines.get("XLA Modules", []) if module_word in ev.name]
        steps += len(modules)
        for ev in lines.get("XLA Ops", []):
            if not any(b <= ev.start_ns < e for b, e in modules):
                continue
            m = _EVENT.match(ev.name)
            op = names.get(m.group(1)) if m else None
            if op is None:
                seconds["unnamed"] += ev.duration_ns / 1e9
                continue
            scope = next((s for s in scopes if re.search(rf"(?:^|[/(]){s}(?:[)/]|$)", op)), "other")
            seconds[scope] += ev.duration_ns / 1e9
    total = sum(seconds.values())
    return {
        "steps": steps,
        "s_a_step": {k: v / max(steps, 1) for k, v in seconds.items()},
        "share": {k: v / total for k, v in seconds.items()} if total else {},
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--trace", required=True)
    parser.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--scopes", default="noise,router,routed_experts,block_diffusion_attention,head")
    args = parser.parse_args()
    import jax

    from chipbench import harness

    cell = harness.Cell(args.benchmark, args.workload)
    load = cell.build_load(jax.devices())
    names = op_names(load.lower_step(load.sharding).compile().as_text())
    planes = jax.profiler.ProfileData.from_file(args.trace).planes
    result = split(planes, names, args.scopes.split(","), "train_step")
    print(json.dumps(dict(result, workload=cell.name, instructions=len(names))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
