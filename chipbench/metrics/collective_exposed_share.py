"""Share of device 0's busy time spent in collective ops that no other op
on device 0 overlaps: the union of the collective ops' intervals, less the
part that non-collective ops cover, over the union of all device-0 op
intervals.  Both are taken inside the capture, so a capture that ends
before the window does (PERF.md) biases neither, but on four chips the
capture holds only the head of the window, and the share is that of the
head's mix of admission and decode.

An op is collective by its HLO opcode, read from the op's ``long_name``
(``%<name> = <shape> <opcode>(<operands>), ...``): ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute`` or
``all-to-all``, or their asynchronous ``-start`` and ``-done`` halves (a
``-done`` op's span is the wait for the transfer).  The name alone does
not do: in the yi-9b programs compiled for a v5e 2x2 (data=1,model=4) the
row-parallel GEMMs' reductions are ``psum.<n>`` (opcode ``all-reduce``,
synchronous), the embedding gather's is ``all-reduce.<n>`` and the
argmax over sharded logits ``all-gather.<n>``.  An op without a
``long_name`` is judged by its name.  A one-device program has no
collective, and the reader returns None."""
import re

from benchlib.trace import merged, union_length

_OPS = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
_OPCODE = re.compile(r"\s" + _OPS + r"(-start|-done)?\(")
_NAME = re.compile(r"^" + _OPS + r"(-start|-done)?(\.\d+)?$")


def is_collective(op) -> bool:
    long_name = op.args.get("long_name")
    if long_name:
        return _OPCODE.search(long_name) is not None
    return _NAME.match(op.name) is not None


def _covered(spans, cover) -> float:
    """Length of ``spans`` (merged, sorted) that ``cover`` (merged,
    sorted) overlaps."""
    total, j = 0.0, 0
    for a, b in spans:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    coll, other = [], []
    for o in t.ops:
        (coll if is_collective(o) else other).append((o.ts, o.ts + o.dur))
    busy = union_length(coll + other)
    if not coll or busy <= 0:
        return None
    spans = merged(coll)
    exposed = union_length(spans) - _covered(spans, merged(other))
    return exposed / busy
