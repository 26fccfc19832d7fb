"""Roofline analysis from compiled HLO (EXPERIMENTS.md §Roofline).

Parses ``compiled.as_text()`` (post-SPMD optimized HLO) into a computation
call graph, scales while-loop bodies by their ``known_trip_count`` (XLA's
cost analysis counts a ``lax.scan`` body ONCE — verified experimentally, see
DESIGN.md §8), and derives the three per-chip roofline terms:

    compute    = dot/conv FLOPs (post-partition shapes are per-device)
    memory     = bytes touched by non-fused ops (operands + outputs)
    collective = ring-cost wire bytes per device of every collective op

The peaks come from the machine file (DESIGN.md §1f): ``analyze`` divides
by the :class:`~repro.machine.machine.Peaks` of the process-wide
:func:`~repro.machine.machine.default_machine` (or an explicit ``machine=``
profile). On an accelerator those are the published peaks of the device
kind (``CHIP_PEAKS``; an unknown kind raises). On the CPU the bundled
default carries the former hardcoded constants, marked as not a chip's;
after ``python -m repro.machine.microbench`` the roofline speaks this
host's sustained rates.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict

from ..machine.machine import DTYPE_BYTES as _DTYPE_BYTES
from ..machine.machine import MachineProfile, default_machine

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _parse_shapes(type_str: str) -> list[tuple[str, tuple[int, ...]]]:
    """All 'dtype[dims]' occurrences in a type string (tuples expanded)."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",") if x) if dims else ()
        out.append((dt, shape))
    return out


def _nbytes(dt: str, shape: tuple[int, ...]) -> int:
    n = _DTYPE_BYTES[dt]
    for s in shape:
        n *= s
    return n


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    out_types: list  # [(dtype, shape)]
    operands: list[str]
    attrs: str


@dataclasses.dataclass
class CollectiveRecord:
    kind: str
    bytes_in: int  # per-device operand bytes (one execution)
    group_size: int
    count: int  # executions per step
    wire_bytes: float  # ring-cost bytes on the wire per device, total


@dataclasses.dataclass
class RooflineReport:
    flops: float  # per device per step
    bytes_hbm: float
    bytes_collective: float  # wire bytes per device
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    collectives: list  # top CollectiveRecords (dicts)
    collective_counts: dict  # kind -> wire bytes

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class HloModule:
    """Parsed optimized-HLO module with execution-count propagation."""

    def __init__(self, text: str):
        self.comps: dict[str, list[Op]] = {}
        self.shape_of: dict[str, tuple[str, tuple[int, ...]]] = {}
        self.entry = None
        self._parse(text)
        self.counts = self._propagate_counts()

    # -- parsing ---------------------------------------------------------
    def _parse(self, text: str) -> None:
        cur = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line or line.startswith("//"):
                continue
            # computation header: `%name (params...) -> type {` — params may
            # nest parens (tuple types), so match greedily and exclude op
            # lines (which contain " = ")
            header = None
            if line.endswith("{") and " = " not in line:
                header = re.match(
                    r"^\s*(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$", line
                )
            if header:
                cur = header.group(2)
                self.comps[cur] = []
                if header.group(1):
                    self.entry = cur
                continue
            if line.strip() == "}":
                continue
            if cur is None:
                continue
            m = _OP_RE.match(line)
            if not m:
                continue
            name, rhs = m.group(1), m.group(2)
            rhs = re.sub(r"/\*.*?\*/", "", rhs).strip()  # strip /*index=N*/ comments
            parsed = self._split_rhs(rhs)
            if parsed is None:
                continue
            type_str, kind, args, attrs = parsed
            operands = re.findall(r"%([\w\.\-]+)", args)
            out_types = _parse_shapes(type_str)
            self.shape_of[name] = out_types[0] if out_types else ("f32", ())
            self.comps[cur].append(Op(name, kind, out_types, operands, attrs))
        if self.entry is None and self.comps:
            self.entry = next(iter(self.comps))

    @staticmethod
    def _split_rhs(rhs: str):
        """'TYPE kind(args), attrs' -> (type, kind, args, attrs).

        TYPE may be a tuple type with nested parens (huge for scan carries),
        so it is consumed with explicit paren balancing, not a regex.
        """
        if rhs.startswith("("):
            depth = 0
            end = -1
            for i, ch in enumerate(rhs):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            if end < 0:
                return None
            type_str = rhs[: end + 1]
            rest = rhs[end + 1 :]
        else:
            tm = re.match(r"^[\w\[\],\.]+(\{[^}]*\})?", rhs)
            if not tm:
                return None
            type_str = tm.group(0)
            rest = rhs[tm.end() :]
        km = re.match(r"\s*([\w\-]+)\((.*)$", rest)
        if not km:
            return None
        kind, tail = km.group(1), km.group(2)
        depth = 1
        args = []
        for i, ch in enumerate(tail):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return type_str, kind, "".join(args), tail[i + 1 :]
            args.append(ch)
        return type_str, kind, "".join(args), ""

    # -- execution counts --------------------------------------------------
    def _propagate_counts(self) -> dict[str, float]:
        counts: dict[str, float] = defaultdict(float)
        fused: set[str] = set()
        counts[self.entry] = 1.0
        # iterate to fixed point (call graph is a DAG; few passes suffice)
        for _ in range(12):
            changed = False
            for comp, c in list(counts.items()):
                for op in self.comps.get(comp, []):
                    trip = 1.0
                    if op.kind == "while":
                        tm = re.search(r'known_trip_count[^\d]*(\d+)', op.attrs)
                        trip = float(tm.group(1)) if tm else 1.0
                        for key in ("body=", "condition="):
                            bm = re.search(key + r"%?([\w\.\-]+)", op.attrs)
                            if bm:
                                tgt = bm.group(1)
                                newc = c * trip
                                if counts.get(tgt, 0) < newc:
                                    counts[tgt] = newc
                                    changed = True
                        continue
                    for key, is_fused in (
                        ("calls=", True), ("to_apply=", True),
                        ("branch_computations=", False),
                    ):
                        am = re.search(key + r"\{?%?([\w\.\-]+)", op.attrs)
                        if am:
                            tgt = am.group(1)
                            if is_fused and op.kind == "fusion":
                                fused.add(tgt)
                            if counts.get(tgt, 0) < c:
                                counts[tgt] = c
                                changed = True
            if not changed:
                break
        self.fused = fused
        return counts

    # -- cost extraction ---------------------------------------------------
    def _dot_flops(self, op: Op) -> float:
        out_elems = 1
        for dt, shape in op.out_types[:1]:
            for s in shape:
                out_elems *= s
        lhs = op.operands[0] if op.operands else None
        cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.attrs)
        contract = 1
        if lhs and lhs in self.shape_of and cdims:
            shape = self.shape_of[lhs][1]
            for d in cdims.group(1).split(","):
                if d:
                    contract *= shape[int(d)] if int(d) < len(shape) else 1
        return 2.0 * out_elems * contract

    def flops(self) -> float:
        total = 0.0
        for comp, ops in self.comps.items():
            c = self.counts.get(comp, 0.0)
            if c == 0:
                continue
            for op in ops:
                if op.kind == "dot":
                    total += c * self._dot_flops(op)
                elif op.kind == "convolution":
                    out_elems = 1
                    for s in op.out_types[0][1]:
                        out_elems *= s
                    ksize = 1
                    if len(op.operands) > 1 and op.operands[1] in self.shape_of:
                        for s in self.shape_of[op.operands[1]][1][:-1]:
                            ksize *= s
                    total += c * 2.0 * out_elems * ksize
        return total

    _SKIP_BYTES = {
        "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "after-all", "partition-id", "replica-id", "iota",
    }

    def bytes_hbm(self) -> float:
        total = 0.0
        for comp, ops in self.comps.items():
            c = self.counts.get(comp, 0.0)
            if c == 0 or comp in self.fused:
                continue  # fused internals don't touch HBM
            for op in ops:
                if op.kind in self._SKIP_BYTES:
                    continue
                b = sum(_nbytes(dt, sh) for dt, sh in op.out_types)
                for o in op.operands:
                    if o in self.shape_of:
                        dt, sh = self.shape_of[o]
                        b += _nbytes(dt, sh)
                total += c * b
        return total

    @staticmethod
    def _group_size(attrs: str) -> int:
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
        if m:
            return int(m.group(2))
        m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
        if m:
            return len(m.group(1).split(","))
        return 1

    def collectives(self) -> list[CollectiveRecord]:
        recs = []
        for comp, ops in self.comps.items():
            c = self.counts.get(comp, 0.0)
            if c == 0:
                continue
            for op in ops:
                kind = next((k for k in _COLLECTIVES if op.kind.startswith(k)), None)
                if kind is None or op.kind.endswith("-done"):
                    continue
                g = self._group_size(op.attrs)
                b_in = 0
                for o in op.operands:
                    if o in self.shape_of:
                        dt, sh = self.shape_of[o]
                        b_in += _nbytes(dt, sh)
                if b_in == 0:  # fall back to output size
                    b_in = sum(_nbytes(dt, sh) for dt, sh in op.out_types)
                if kind == "all-gather":
                    wire = (g - 1) * b_in
                elif kind == "all-reduce":
                    wire = 2 * (g - 1) / max(g, 1) * b_in
                elif kind == "reduce-scatter":
                    wire = (g - 1) / max(g, 1) * b_in
                elif kind == "all-to-all":
                    wire = (g - 1) / max(g, 1) * b_in
                else:  # collective-permute
                    wire = b_in
                recs.append(
                    CollectiveRecord(
                        kind=kind, bytes_in=b_in, group_size=g, count=int(c),
                        wire_bytes=wire * c,
                    )
                )
        return recs


def analyze(
    hlo_text: str, machine: "MachineProfile | None" = None
) -> RooflineReport:
    peaks = (machine if machine is not None else default_machine()).peaks
    mod = HloModule(hlo_text)
    flops = mod.flops()
    bts = mod.bytes_hbm()
    colls = mod.collectives()
    cbytes = sum(r.wire_bytes for r in colls)
    by_kind: dict[str, float] = defaultdict(float)
    for r in colls:
        by_kind[r.kind] += r.wire_bytes
    t_c = flops / peaks.flops
    t_m = bts / peaks.hbm_bw
    t_x = cbytes / peaks.ici_bw
    dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)), key=lambda t: t[1])[0]
    top = sorted(colls, key=lambda r: -r.wire_bytes)[:12]
    return RooflineReport(
        flops=flops, bytes_hbm=bts, bytes_collective=cbytes,
        t_compute=t_c, t_memory=t_m, t_collective=t_x, dominant=dom,
        collectives=[dataclasses.asdict(r) for r in top],
        collective_counts=dict(by_kind),
    )


def model_flops(cfg, kind: str, seq_len: int, global_batch: int) -> float:
    """Analytic MODEL_FLOPS for the whole step (all chips): 6·N·D train /
    2·N·D inference, plus the attention term."""
    n = cfg.active_param_count
    if kind == "train":
        tokens = seq_len * global_batch
        base = 6.0 * n * tokens
        attn = 12.0 * cfg.num_layers * cfg.num_heads * cfg.hd * seq_len * seq_len * global_batch
        if cfg.sliding_window:
            attn *= min(1.0, cfg.sliding_window / seq_len)
        if cfg.family in ("ssm", "hybrid"):
            attn = 0.0
        return base + attn
    if kind == "prefill":
        tokens = seq_len * global_batch
        attn = 4.0 * cfg.num_layers * cfg.num_heads * cfg.hd * seq_len * seq_len * global_batch
        if cfg.sliding_window:
            attn *= min(1.0, cfg.sliding_window / seq_len)
        if cfg.family in ("ssm", "hybrid"):
            attn = 0.0
        return 2.0 * n * tokens + attn
    # decode: one token against seq_len of context
    ctx_len = seq_len if not cfg.sliding_window else min(seq_len, cfg.sliding_window)
    attn = 4.0 * cfg.num_layers * cfg.num_heads * cfg.hd * ctx_len * global_batch
    if cfg.family == "ssm":
        attn = 0.0
    return 2.0 * n * global_batch + attn
