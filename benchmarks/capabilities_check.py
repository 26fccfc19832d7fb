"""Capabilities-table drift check (ISSUE 4 satellite, a CI gate).

    PYTHONPATH=src python -m benchmarks.capabilities_check

Prints the ``engine.capabilities()`` op x substrate table and fails (exit
1) when the table and the raw kernel registry drift apart:

- an op registered with no kernel on any substrate (unservable OpSpec),
- a kernel registered under a substrate kind no registered substrate
  serves (unreachable kernel — usually a typo in ``@kernel(..., kind)``),
- a capabilities cell disagreeing with per-instance kernel resolution
  (``Substrate.kernel`` must succeed exactly where the table says True),
- the live table drifting from the pinned :data:`EXPECTED_CAPABILITIES`
  baseline — gaining or losing an ``(op, substrate)`` pair is a conscious
  edit here, not a silent side effect of a registration change.
"""
from __future__ import annotations

import sys

from repro.engine import (
    OpNotSupportedError,
    capabilities,
    default_registry,
    get_substrate,
    list_substrates,
)


# The pinned support matrix: pallas serves GSANA only (kernels/topk_sim);
# moe_dispatch stays local/mesh-only by design.
EXPECTED_CAPABILITIES = {
    "spmv": {"local": True, "mesh": True, "pallas": False},
    "bfs": {"local": True, "mesh": True, "pallas": False},
    "gsana": {"local": True, "mesh": True, "pallas": True},
    "moe_dispatch": {"local": True, "mesh": True, "pallas": False},
}


def check() -> list[str]:
    reg = default_registry()
    table = capabilities()
    errors: list[str] = []
    subs = list_substrates()
    served_kinds = {get_substrate(s).substrate_kind for s in subs}

    for op_name in reg.ops():
        if op_name not in table:
            errors.append(f"op {op_name!r} missing from capabilities table")
    for op_name, row in table.items():
        if not any(row.values()):
            errors.append(f"op {op_name!r} has no kernel on any substrate")
        for sub_name, claimed in row.items():
            sub = get_substrate(sub_name)
            try:
                sub.kernel(op_name)
                resolved = True
            except OpNotSupportedError:
                resolved = False
            if resolved != claimed:
                errors.append(
                    f"drift: capabilities[{op_name!r}][{sub_name!r}] = {claimed} "
                    f"but kernel resolution says {resolved}"
                )
    for op_name, expected_row in EXPECTED_CAPABILITIES.items():
        live_row = {s: table.get(op_name, {}).get(s) for s in expected_row}
        if live_row != expected_row:
            errors.append(
                f"baseline drift: capabilities[{op_name!r}] = {live_row} "
                f"but the pinned baseline says {expected_row} "
                "(update EXPECTED_CAPABILITIES if this change is intended)"
            )
    for op_name, kind in reg.kernels():
        if kind not in served_kinds:
            errors.append(
                f"kernel ({op_name!r}, {kind!r}) registered under a kind no "
                f"substrate serves (kinds: {sorted(served_kinds)})"
            )
    return errors


def main() -> None:
    table = capabilities()
    subs = list_substrates()
    width = max(len(op) for op in table) + 2
    print("op".ljust(width) + "  ".join(s.ljust(8) for s in subs))
    for op_name in sorted(table):
        cells = ("yes" if table[op_name][s] else "-" for s in subs)
        print(op_name.ljust(width) + "  ".join(c.ljust(8) for c in cells))
    errors = check()
    if errors:
        for err in errors:
            print(f"DRIFT: {err}", file=sys.stderr)
        sys.exit(1)
    print(f"# capabilities OK: {len(table)} ops x {len(subs)} substrates, "
          f"{len(default_registry().kernels())} kernels")


if __name__ == "__main__":
    main()
