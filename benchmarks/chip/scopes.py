"""Device time by model layer: each device op of a trace is named by the
``jax.named_scope`` on its path, and its self time is summed by scope.

The program names its layers with the scopes in ``SCOPES`` (the list in
``repro/models/layers.py``, copied here: what a metric reads is part of the
yardstick).  A device op in the trace carries only its HLO instruction
name, so ``attach`` maps it, within its module, to that instruction's
``op_name`` in the compiled program's text; a fusion takes its own
``op_name``, else its root's.  Then, per jitted call inside the window:

* an op's self time is its duration less the time that ops nested inside
  it cover, so a layer scan's ``while`` counts once beside its body;
* an op goes to the innermost listed scope on its ``op_name`` path, else
  to ``unscoped``;
* an unscoped op whose result has the dtype and shape of a leaf of the
  model's decode cache, stacked over layers or of one layer, goes to
  ``cache``: copies the compiler adds carry no ``op_name``.  Its seconds
  are also kept apart, under ``cache_by_shape``;
* coverage is the scoped self time over the call's device time.

``python3 benchmarks/chip/profile_scopes.py`` runs it on a cell.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Set, Tuple

from benchmarks.chip import trace_reduce

SCOPES = ("embed", "layers", "attn", "mlp", "ssm", "moe", "rglru", "cross",
          "cache", "head")
UNSCOPED = "unscoped"

_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_TYPE = re.compile(r"^([a-z]\w*)\[([\d,]*)\]")
_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
              "float64": "f64", "int32": "s32", "int8": "s8",
              "uint8": "u8", "bool": "pred"}


def module_of(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    if not m:
        raise ValueError("no HloModule line in the program's text")
    return m.group(1)


def _operands(rhs: str) -> List[str]:
    """The operand names of an instruction's right-hand side."""
    m = _OPCODE.search(rhs)
    if not m:
        return []
    start = m.end() - 1
    depth, end = 0, len(rhs)
    for i in range(start, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if depth == 0:
            end = i
            break
    return _OPERAND.findall(rhs[start:end])


def op_names(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> ``op_name`` for every instruction of a compiled
    module.  A fusion without one of its own takes its root's; where the
    root has none (a tuple, convert or bitcast the compiler added), the
    nearest instruction below the root that has one."""
    names: Dict[str, Optional[str]] = {}
    operands: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    fusions: List[Tuple[str, str]] = []
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            h = _HEAD.match(line)
            if h:
                comp = h.group(1)
            continue
        name, rhs = m.group(2), m.group(3)
        op = _OP_NAME.search(rhs)
        names[name] = op.group(1) if op else None
        operands[name] = _operands(rhs)
        if m.group(1):
            roots[comp] = name
        calls = _CALLS.search(rhs)
        if calls and " fusion(" in line and op is None:
            fusions.append((name, calls.group(1)))
    for name, called in fusions:
        todo, seen = [roots.get(called)], set()
        while todo and names[name] is None:
            x = todo.pop(0)
            if x in seen or x not in names:
                continue
            seen.add(x)
            names[name] = names[x]
            todo += operands[x]
    return names


def instruction(event_name: str) -> str:
    """'%fusion.24 = bf16[8,1024]{...} fusion(...)' -> 'fusion.24'."""
    return trace_reduce.op_label(event_name).split(" ")[0].lstrip("%")


def attach(ev: Dict[str, List], hlo_texts: Iterable[str]) -> Dict[str, List]:
    """The events with each device op's ``op_name`` appended (None where
    its module's text is not given or has no such instruction)."""
    by_module = {module_of(t): op_names(t) for t in hlo_texts}
    mods = defaultdict(list)
    for chip, name, s, e in ev["modules"]:
        mods[chip].append((s, e, trace_reduce.module_name(name)))
    for v in mods.values():
        v.sort()
    starts = {c: [s for s, _, _ in v] for c, v in mods.items()}
    ops = []
    for chip, name, s, e in ev["ops"]:
        i = bisect.bisect_right(starts.get(chip, []), s) - 1
        op = None
        if i >= 0 and mods[chip][i][1] >= e:
            table = by_module.get(mods[chip][i][2], {})
            op = table.get(instruction(name))
        ops.append([chip, name, s, e, op])
    return dict(ev, ops=ops)


def self_times(spans: List[Tuple[float, float]]) -> List[float]:
    """Each span's length less what the spans nested in it cover."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    own = [e - s for s, e in spans]
    stack: List[List] = []          # [index, covered until]
    for i in order:
        s, e = spans[i]
        while stack and spans[stack[-1][0]][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            pe = spans[parent[0]][1]
            lo, hi = max(s, parent[1]), min(e, pe)
            if hi > lo:
                own[parent[0]] -= hi - lo
                parent[1] = hi
        stack.append([i, s])
    return own


def bucket(op_name: Optional[str], scopes: Tuple[str, ...] = SCOPES) -> str:
    """The innermost of ``scopes`` on the op's path, else ``unscoped``."""
    for part in reversed((op_name or "").split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def result_type(event_name: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    """('bf16', (24, 8, 576, 8, 80)) from an op's event name."""
    parts = trace_reduce.op_label(event_name).split(" ")
    m = _TYPE.match(parts[1]) if len(parts) > 1 else None
    if not m:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def cache_shapes(cache_tree) -> Set[Tuple[str, Tuple[int, ...]]]:
    """Dtype and shape of each leaf of a decode cache (from
    ``jax.eval_shape(model.init_cache ...)``), stacked and of one layer."""
    import jax

    out = set()
    for leaf in jax.tree.leaves(cache_tree):
        dt = _HLO_DTYPE.get(str(leaf.dtype), str(leaf.dtype))
        shape = tuple(int(d) for d in leaf.shape)
        out |= {(dt, shape), (dt, shape[1:])}
    return out


def summarize(ev: Dict[str, List], cache_leaves=frozenset(),
              scopes: Tuple[str, ...] = SCOPES, top: int = 10
              ) -> Dict[str, Dict]:
    """Per jitted call inside the window: its count and device time, the
    self time of each bucket, the ops left unscoped (top ``top``, named as
    ``breakdown.device_ops`` names them), all in seconds per call, and
    its coverage.  ``ev`` is what ``attach`` returns."""
    wins = [(s, e) for n, s, e in ev["host"] if n == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' annotation, got {len(wins)}")
    wlo, whi = wins[0]
    chips = sorted({x[0] for x in ev["ops"]} | {x[0] for x in ev["modules"]})
    if not chips:
        raise ValueError("the trace holds no device events")
    chip = chips[0]
    mods = sorted((s, e, trace_reduce.module_name(n))
                  for c, n, s, e in ev["modules"]
                  if c == chip and min(e, whi) > max(s, wlo))
    starts = [s for s, _, _ in mods]
    ops = [x for x in ev["ops"] if x[0] == chip]
    own = self_times([(s, e) for _, _, s, e, _ in ops])
    calls: Dict[str, Dict] = {}
    for s, e, name in mods:
        c = calls.setdefault(name, {"count": 0, "device_s": 0.0,
                                    "self_s": defaultdict(float),
                                    "cache_by_shape_s": 0.0,
                                    "unscoped_ops": defaultdict(float)})
        c["count"] += 1
        c["device_s"] += (min(e, whi) - max(s, wlo)) / 1e9
    for (_, name, s, e, op), t in zip(ops, own):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or mods[i][1] < e:
            continue
        c = calls[mods[i][2]]
        b = bucket(op, scopes)
        if b == UNSCOPED and result_type(name) in cache_leaves:
            b = "cache"
            c["cache_by_shape_s"] += t / 1e9
        elif b == UNSCOPED:
            c["unscoped_ops"][trace_reduce.op_label(name)] += t / 1e9
        c["self_s"][b] += t / 1e9
    out = {}
    for name, c in calls.items():
        n = c["count"]
        scoped = sum(v for k, v in c["self_s"].items() if k != UNSCOPED)
        out[name] = {
            "count": n,
            "device_s": c["device_s"] / n,
            "self_s": {k: v / n for k, v in sorted(c["self_s"].items())},
            "cache_by_shape_s": c["cache_by_shape_s"] / n,
            "unscoped_ops": sorted(
                ([k, v / n] for k, v in c["unscoped_ops"].items()),
                key=lambda kv: -kv[1])[:top],
            "coverage": scoped / c["device_s"] if c["device_s"] else None,
        }
    return out
