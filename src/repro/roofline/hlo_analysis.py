"""Optimized-HLO analyzer: FLOPs / HBM bytes / collective bytes per device.

Why not ``compiled.cost_analysis()`` alone: on this backend it counts a
``while`` (scan) body ONCE, so any scan-over-layers model under-reports
FLOPs by ~n_layers x (verified empirically — see EXPERIMENTS.md §Dry-run).
We parse ``compiled.as_text()`` instead and apply loop trip-count
multipliers. After SPMD partitioning every shape in the module is already
the per-device shard, so all totals below are per-device numbers.

Model:
  * flops       — 2 * prod(out_dims) * prod(lhs contracting dims) for every
                  ``dot`` (recursing into fusion-called computations);
                  while bodies multiplied by their trip count
                  (backend_config known_trip_count, fallback: the cond's
                  compare constant).
  * bytes       — Σ over *top-level* instructions of operand + result
                  buffer sizes. Fusions count their boundary operands and
                  results only (internals live in registers/cache): the
                  post-fusion HBM-traffic model. parameter/constant/tuple/
                  get-tuple-element/bitcast are excluded (no traffic).
  * collectives — wire bytes *received per device*, per op:
                      all-reduce          2 (g-1)/g * bytes   (ring)
                      all-gather          (g-1)/g * out_bytes
                      reduce-scatter      (g-1)/g * in_bytes
                      all-to-all          (g-1)/g * bytes
                      collective-permute  1.0 * bytes
                  with g = replica-group size parsed from the op.
  * conditional — branch costs are AVERAGED (a 2-branch compute/skip cond,
                  e.g. the causal block-skip optimization, then counts
                  ~50% live — matching the causal triangle's live
                  fraction). Recorded so the block-skip hillclimb is
                  visible in the compute term.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["Cost", "HloModule", "parse_hlo", "analyze_module",
           "compile_and_cost", "collective_summary", "tpu_kernel_counts"]

_ESIZE = {"f64": 8, "s64": 8, "u64": 8, "c64": 8,
          "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
          "s8": 1, "u8": 1, "pred": 1,
          "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
          "s4": 1, "u4": 1, "token": 0, "opaque": 0}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_NO_TRAFFIC = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "partition-id", "replica-id", "iota",
               "reshape"}


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_op: dict = field(default_factory=dict)
    bytes_by_tag: dict = field(default_factory=dict)   # named_scope -> bytes
    int8_flops: float = 0.0    # subset of flops on s8xs8 dots (2x MXU peak)

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.coll_bytes += o.coll_bytes
        self.int8_flops += o.int8_flops
        for k, v in o.coll_by_op.items():
            self.coll_by_op[k] = self.coll_by_op.get(k, 0.0) + v
        for k, v in o.bytes_by_tag.items():
            self.bytes_by_tag[k] = self.bytes_by_tag.get(k, 0.0) + v
        return self

    def scaled(self, m: float) -> "Cost":
        return Cost(self.flops * m, self.bytes * m, self.coll_bytes * m,
                    {k: v * m for k, v in self.coll_by_op.items()},
                    {k: v * m for k, v in self.bytes_by_tag.items()},
                    self.int8_flops * m)


# named_scope markers the model code emits; bytes attributed by substring
# match on the instruction's op_name metadata. Used by §Perf to quantify
# what the fused Pallas kernels remove from HBM traffic.
TAGS = ("flash_attn", "decode_attn", "full_attn", "moe_dispatch", "ssd_scan")


# --------------------------------------------------------------------------
# shape / type parsing
# --------------------------------------------------------------------------

def _split_top(s: str) -> list[str]:
    """Split a tuple-type body on top-level commas."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur).strip())
    return out


_SHAPE_RE = re.compile(r"^([a-z0-9]+)\[([\d,]*)\]")


def parse_shape(t: str):
    """'f32[4,16,64]{2,1,0}' -> ('f32', (4,16,64)). Tuples -> list of both."""
    t = t.strip()
    if t.startswith("("):
        inner = t[1:t.rindex(")")]
        return [parse_shape(e) for e in _split_top(inner)]
    m = _SHAPE_RE.match(t)
    if not m:
        return ("opaque", ())
    dt, dims = m.group(1), m.group(2)
    shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
    return (dt, shape)


def type_bytes(t: str) -> float:
    p = parse_shape(t)
    items = p if isinstance(p, list) else [p]
    total = 0.0
    for it in items:
        if isinstance(it, list):       # nested tuple
            total += sum(_elem_bytes(x) for x in _flatten(it))
        else:
            total += _elem_bytes(it)
    return total


def _flatten(x):
    for it in x:
        if isinstance(it, list):
            yield from _flatten(it)
        else:
            yield it


def _elem_bytes(p) -> float:
    dt, shape = p
    n = 1
    for d in shape:
        n *= d
    return n * _ESIZE.get(dt, 4)


# --------------------------------------------------------------------------
# module parsing
# --------------------------------------------------------------------------

@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    operands: list[str]
    attrs: str


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    sig_params: dict = field(default_factory=dict)   # name -> type str
    is_entry: bool = False


@dataclass
class HloModule:
    computations: dict = field(default_factory=dict)
    entry: str = ""


_COMP_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")


def _parse_instr_rhs(rhs: str):
    """rhs = '<type> <opcode>(<operands>), attrs...'."""
    rhs = rhs.strip()
    if rhs.startswith("("):            # tuple type: find matching paren
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str = rhs[: i + 1]
        rest = rhs[i + 1:].strip()
    else:
        sp = rhs.index(" ")
        type_str = rhs[:sp]
        rest = rhs[sp + 1:].strip()
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return type_str, rest, [], ""
    opcode = m.group(1)
    # operand list: balanced parens from opcode's '('
    start = m.end() - 1
    depth = 0
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                break
    ops_str = rest[start + 1: i]
    attrs = rest[i + 1:]
    operands = [o.strip() for o in _split_top(ops_str)] if ops_str else []
    return type_str, opcode, operands, attrs


def parse_hlo(text: str) -> HloModule:
    mod = HloModule()
    cur: Computation | None = None
    for line in text.splitlines():
        if not line.strip():
            continue
        if not line.startswith(" "):                    # computation head
            mh = _COMP_HEAD.match(line)
            if mh:
                is_entry = bool(mh.group(1))
                name = mh.group(2)
                cur = Computation(name=name, is_entry=is_entry)
                # signature params: "a: f32[2], b: (s32[], f32[3])"
                for p in _split_top(mh.group(3)):
                    if ":" in p:
                        pn, pt = p.split(":", 1)
                        cur.sig_params[pn.strip().lstrip("%")] = pt.strip()
                mod.computations[name] = cur
                if is_entry:
                    mod.entry = name
                continue
            if line.startswith("}"):
                cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name, rhs = mi.group(1), mi.group(2)
        try:
            type_str, opcode, operands, attrs = _parse_instr_rhs(rhs)
        except Exception:
            continue
        cur.instrs.append(Instr(name, type_str, opcode, operands, attrs))
    return mod


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------

_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_BRACKET = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACES = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_CDIMS = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_BODY = re.compile(r"body=%?([\w.\-]+)")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TOAPPLY = re.compile(r"to_apply=%?([\w.\-]+)")


def _group_size(attrs: str, default: int = 1) -> int:
    m = _GROUPS_BRACKET.search(attrs)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACES.search(attrs)
    if m:
        return len(m.group(1).split(","))
    return default


def _symbol_table(comp: Computation) -> dict:
    tab = dict(comp.sig_params)
    for ins in comp.instrs:
        tab[ins.name] = ins.type_str
    return tab


def _operand_type(op: str, tab: dict) -> str | None:
    # operand may be "%name" or "f32[2,3] %name" (older dialect)
    op = op.strip()
    if op.startswith("%"):
        return tab.get(op[1:])
    parts = op.rsplit("%", 1)
    if len(parts) == 2 and parts[0].strip():
        return parts[0].strip()
    return tab.get(op.lstrip("%"))


def _dot_flops(ins: Instr, tab: dict) -> tuple[float, bool]:
    """Returns (flops, is_int8) for a dot/convolution instruction."""
    out = parse_shape(ins.type_str)
    if isinstance(out, list):
        return 0.0, False
    out_elems = 1
    for d in out[1]:
        out_elems *= d
    k = 1
    is_int8 = False
    m = _CDIMS.search(ins.attrs)
    lhs_t = _operand_type(ins.operands[0], tab) if ins.operands else None
    if m and lhs_t:
        lhs = parse_shape(lhs_t)
        if not isinstance(lhs, list):
            for idx in (int(i) for i in m.group(1).split(",") if i):
                if idx < len(lhs[1]):
                    k *= lhs[1][idx]
            is_int8 = lhs[0] in ("s8", "u8")
    return 2.0 * out_elems * k, is_int8


def _collective_bytes(ins: Instr, tab: dict) -> float:
    g = _group_size(ins.attrs)
    if g <= 1:
        return 0.0
    opcode = ins.opcode.replace("-start", "")
    out_b = type_bytes(ins.type_str)
    in_b = sum(type_bytes(_operand_type(o, tab) or "f32[]")
               for o in ins.operands)
    frac = (g - 1) / g
    if opcode == "all-reduce":
        return 2.0 * frac * out_b
    if opcode == "all-gather":
        return frac * out_b
    if opcode == "reduce-scatter":
        return frac * in_b
    if opcode == "all-to-all":
        return frac * max(in_b, out_b)
    if opcode == "collective-permute":
        return out_b
    return 0.0


def _fusion_flops(comp: Computation, mod: HloModule,
                  memo: dict) -> tuple[float, float]:
    """(flops, int8_flops) inside a fused computation (dots; recursive)."""
    if comp.name in memo:
        return memo[comp.name]
    tab = _symbol_table(comp)
    total = i8 = 0.0
    for ins in comp.instrs:
        if ins.opcode in ("dot", "convolution"):
            f, is8 = _dot_flops(ins, tab)
            total += f
            if is8:
                i8 += f
        elif ins.opcode == "fusion":
            m = _CALLS.search(ins.attrs)
            if m and m.group(1) in mod.computations:
                f, fi8 = _fusion_flops(mod.computations[m.group(1)], mod,
                                       memo)
                total += f
                i8 += fi8
    memo[comp.name] = (total, i8)
    return total, i8


def _operand_name(op: str) -> str:
    """'%c', 's32[] %c' or bare 'c' -> 'c'."""
    return op.strip().rsplit("%", 1)[-1].strip()


def _const_int(ins: Instr | None) -> int | None:
    """Integer literal of a parsed constant: ``%c = s32[] constant(5)``
    parses with the value as the constant's sole *operand* (not in attrs
    or the type string), so that is where the bound lives."""
    if ins is None or ins.opcode != "constant" or not ins.operands:
        return None
    lit = ins.operands[0].strip()
    return int(lit) if lit.lstrip("-").isdigit() else None


def _trip_count(ins: Instr, mod: HloModule) -> int:
    m = _TRIP_RE.search(ins.attrs)
    if m:
        return int(m.group(1))
    # fallback for modules whose backend_config lost known_trip_count: a
    # counted loop's cond computation compares the induction variable
    # against a constant bound — resolve the compare's operands to
    # constant instructions and read the bound from there.
    mc = _COND.search(ins.attrs)
    if mc and mc.group(1) in mod.computations:
        cond = mod.computations[mc.group(1)]
        consts = {ci.name: ci for ci in cond.instrs
                  if ci.opcode == "constant"}
        for ci in cond.instrs:
            if ci.opcode != "compare":
                continue
            for op in ci.operands:
                n = _const_int(consts.get(_operand_name(op)))
                if n is not None and n > 0:
                    return n
        # no compare resolved: any positive int constant in the cond
        for ci in consts.values():
            n = _const_int(ci)
            if n is not None and n > 0:
                return n
    return 1


def _comp_cost(comp: Computation, mod: HloModule, memo: dict,
               fusion_memo: dict) -> Cost:
    if comp.name in memo:
        return memo[comp.name]
    memo[comp.name] = Cost()           # cycle guard
    tab = _symbol_table(comp)
    c = Cost()
    for ins in comp.instrs:
        op = ins.opcode
        if op == "while":
            body = _BODY.search(ins.attrs)
            trip = _trip_count(ins, mod)
            if body and body.group(1) in mod.computations:
                c += _comp_cost(mod.computations[body.group(1)], mod, memo,
                                fusion_memo).scaled(trip)
            continue
        if op == "conditional":
            mb = _BRANCHES.search(ins.attrs)
            names = []
            if mb:
                names = [n.strip().lstrip("%")
                         for n in mb.group(1).split(",")]
            else:
                names = [m for m in re.findall(r"%([\w.\-]+)", ins.attrs)
                         if m in mod.computations]
            branch_costs = [
                _comp_cost(mod.computations[n], mod, memo, fusion_memo)
                for n in names if n in mod.computations]
            if branch_costs:
                avg = Cost()
                for bc in branch_costs:
                    avg += bc
                c += avg.scaled(1.0 / len(branch_costs))
            continue
        if op == "call":
            m = _TOAPPLY.search(ins.attrs)
            if m and m.group(1) in mod.computations:
                c += _comp_cost(mod.computations[m.group(1)], mod, memo,
                                fusion_memo)
            continue
        if op in ("dot", "convolution"):
            f, is8 = _dot_flops(ins, tab)
            c.flops += f
            if is8:
                c.int8_flops += f
        elif op == "fusion":
            m = _CALLS.search(ins.attrs)
            if m and m.group(1) in mod.computations:
                f, fi8 = _fusion_flops(mod.computations[m.group(1)], mod,
                                       fusion_memo)
                c.flops += f
                c.int8_flops += fi8
        elif any(op.startswith(col) for col in _COLLECTIVES):
            if op.endswith("-done"):
                continue
            cb = _collective_bytes(ins, tab)
            c.coll_bytes += cb
            key = op.replace("-start", "")
            c.coll_by_op[key] = c.coll_by_op.get(key, 0.0) + cb
        # HBM bytes: boundary traffic of every top-level op
        if op not in _NO_TRAFFIC and not op.endswith("-done"):
            b = _instr_traffic(ins, tab, mod)
            c.bytes += b
            for tag in TAGS:
                if tag in ins.attrs:      # op_name metadata substring
                    c.bytes_by_tag[tag] = c.bytes_by_tag.get(tag, 0.0) + b
                    break
    memo[comp.name] = c
    return c


_SPARSE_OPS = ("dynamic-update-slice", "dynamic-slice", "gather", "scatter")


def _instr_traffic(ins: Instr, tab: dict, mod: HloModule) -> float:
    """HBM traffic model for one op. Sparse-access ops touch only the
    moved slice, not their full operands (XLA aliases DUS in place inside
    loops; gathers read only the selected rows):
      * dynamic-update-slice — read+write of the inserted slice,
      * dynamic-slice / gather — 2 x result,
      * scatter — 2 x updates operand.
    Fusions wrapping one of these (wrapped_scatter/gather etc.) are
    classified by their called computation's root op. Everything else:
    result + all operands (post-fusion boundary model).
    """
    op = ins.opcode
    if op == "fusion":
        m = _CALLS.search(ins.attrs)
        if m and m.group(1) in mod.computations:
            called = mod.computations[m.group(1)]
            has_sparse = any(i.opcode in _SPARSE_OPS for i in called.instrs)
            if has_sparse:
                # the fusion streams a slice of (or into) its largest
                # buffer; the big buffers alias/loop in place. Count 2x
                # everything well below the largest candidate.
                res_b = type_bytes(ins.type_str)
                cand = [res_b] + [
                    type_bytes(_operand_type(o, tab) or "f32[]")
                    for o in ins.operands]
                big = max(cand)
                small = sum(c for c in cand if c < 0.25 * big)
                return 2.0 * small if small else 2.0 * min(cand)
    if op == "dynamic-update-slice":
        upd = _operand_type(ins.operands[1], tab) if len(ins.operands) > 1 \
            else None
        return 2.0 * type_bytes(upd) if upd else 0.0
    if op in ("dynamic-slice", "gather"):
        return 2.0 * type_bytes(ins.type_str)
    if op == "scatter":
        upd = _operand_type(ins.operands[-1], tab) if ins.operands else None
        return 2.0 * type_bytes(upd) if upd else type_bytes(ins.type_str)
    b = type_bytes(ins.type_str)
    for o in ins.operands:
        t = _operand_type(o, tab)
        if t:
            b += type_bytes(t)
    return b


def analyze_module(hlo_text: str) -> Cost:
    """Per-device Cost for one compiled executable."""
    mod = parse_hlo(hlo_text)
    if not mod.entry:
        return Cost()
    return _comp_cost(mod.computations[mod.entry], mod, {}, {})


def compile_and_cost(fn, *args, **kwargs):
    """Lower + compile ``fn`` on ``args`` and cost the optimized HLO.

    Returns ``(cost, compiled)``. The compiled executable is handed back
    deliberately: the serving control plane's cost model prices every
    ladder bucket by compiling it, and the same executable then *serves*
    that bucket AOT — one compile pays for both costing and warm-up
    instead of a second jit trace of the identical function.

    ``fn`` may be a ``jax.jit`` wrapper (anything with ``.lower``) or a
    plain callable, which is jitted here. jax import is deferred so the
    text parser above stays importable without a jax install.
    """
    import jax

    lowered = (fn.lower(*args, **kwargs) if hasattr(fn, "lower")
               else jax.jit(fn).lower(*args, **kwargs))
    compiled = lowered.compile()
    return analyze_module(compiled.as_text()), compiled


def collective_summary(cost: Cost) -> str:
    if not cost.coll_by_op:
        return "none"
    return ", ".join(f"{k}={v / 1e6:.1f}MB"
                     for k, v in sorted(cost.coll_by_op.items()))


_KERNEL_NAME = re.compile(r'/(\w+)/pallas_call"')


def tpu_kernel_counts(hlo_text: str) -> dict[str, int]:
    """{kernel name: compiled Pallas TPU custom calls} in an optimized HLO
    text — each ``tpu_custom_call`` carries its ``pallas_call(name=...)``
    in its op_name metadata. Empty for interpret-mode or CPU programs,
    which lower the kernels to plain HLO."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _KERNEL_NAME.search(line)
        name = m.group(1) if m else "?"
        counts[name] = counts.get(name, 0) + 1
    return counts
