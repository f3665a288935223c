"""KGCT002 host-sync-in-hot-path: no hidden device->host syncs in step().

Every ``.item()`` / ``jax.device_get`` / ``.block_until_ready()`` reachable
from an Engine class's ``step``/``_step*`` methods stalls the dispatch
pipeline for a full host round trip while the device sits idle (bench
measures the trip on its own device). The ONE sanctioned sync per step
lives inside ``with ph("device_fetch")``, where phase attribution makes its cost
visible in /metrics; a sync anywhere else on the hot path is an invisible
TTFT/TPOT tax. ``float()``/``int()``/``bool()`` on a compiled step
program's result is the same sync in implicit clothing.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from ..core import Finding, LintModule, Rule, _dotted

SYNC_METHOD_CALLS = frozenset({"item", "block_until_ready"})
SYNC_DOTTED = frozenset({"jax.device_get"})
IMPLICIT_SYNC_BUILTINS = frozenset({"float", "int", "bool"})
_STEP_FN_ATTR = re.compile(r"^_\w+_fn$")


class HostSyncRule(Rule):
    code = "KGCT002"
    name = "host-sync-in-hot-path"
    description = (".item()/device_get/block_until_ready (or implicit "
                   "float()/bool() on step-program outputs) reachable from "
                   "Engine.step outside the device_fetch phase window")

    def check(self, mod: LintModule) -> Iterator[Finding]:
        for fn in mod.hot_path_functions:
            # Names bound from compiled-step-program calls in this function:
            # float()/int()/bool() on these is an implicit device sync.
            device_names: set = set()
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                val = node.value
                if (isinstance(val, ast.Call)
                        and isinstance(val.func, ast.Attribute)
                        and isinstance(val.func.value, ast.Name)
                        and val.func.value.id == "self"
                        and _STEP_FN_ATTR.match(val.func.attr)):
                    for tgt in node.targets:
                        for leaf in ast.walk(tgt):
                            if isinstance(leaf, ast.Name):
                                device_names.add(leaf.id)

            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = node.func
                sync = None
                if (isinstance(callee, ast.Attribute)
                        and callee.attr in SYNC_METHOD_CALLS):
                    sync = f".{callee.attr}()"
                elif _dotted(callee) in SYNC_DOTTED:
                    sync = _dotted(callee)
                elif (isinstance(callee, ast.Name)
                      and callee.id in IMPLICIT_SYNC_BUILTINS
                      and node.args
                      and isinstance(node.args[0], ast.Name)
                      and node.args[0].id in device_names):
                    sync = f"{callee.id}() on step-program output"
                if sync is None:
                    continue
                if mod.inside_phase_block(node, "device_fetch"):
                    continue    # the sanctioned, phase-attributed sync point
                yield self.finding(
                    mod, node,
                    f"host sync {sync} in hot-path {fn.name!r} outside a "
                    "with ph(\"device_fetch\") window — stalls dispatch "
                    "unattributed; move it into the fetch phase or off the "
                    "step path")
