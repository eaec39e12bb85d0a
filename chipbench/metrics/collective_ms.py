"""Device time of the collectives per field-step: on each chip the busy
union of the ops that are collectives, clipped to the window, averaged
over the chips (device trace).

An op's name in the trace is its HLO instruction as text, ``%<name> =
<shape> <opcode>(<operands>), ...``.  An op is a collective when its
opcode is one, whatever its name (``%psum.7 = s32[1024]{0}
all-reduce(...)``), or when its name begins with one
(``%all-gather-start.1 = ...``).  A fusion that merely takes a collective's
result as an operand (``%fusion.7 = f32[] fusion(%all-reduce.3)``) is not
one."""
import re

from chipbench import tracing

_OPS = (r"(all-reduce|all-gather|reduce-scatter|all-to-all"
        r"|collective-permute|collective-broadcast)(-start|-done)?")
# The shape is one token, or a tuple in parentheses.
_BY_OPCODE = re.compile(r"^%?\S+ = (?:\(.*?\)|\S+) " + _OPS + r"\(")
_BY_NAME = re.compile(r"^%?" + _OPS + r"[.\d-]*(\s|=|$)")


def is_collective(name: str) -> bool:
    return bool(_BY_NAME.match(name) or _BY_OPCODE.match(name))


def read(ctx):
    if ctx.mode != "write" or ctx.trace is None or not ctx.units:
        return None
    t = ctx.trace
    tot = sum(b - a for p in t.ops for a, b in tracing.union(
        ((e.t0, e.t1) for e in t.ops[p] if is_collective(e.name)),
        t.lo, t.hi))
    return tot * 1e-6 / len(t.ops) / len(ctx.units)
