"""Trace arithmetic that stays linear in the number of operations, for the
four-chip cell's readers: a 45 s window there holds some hundreds of
thousands of operations a chip, thousands of them all-to-alls.

``bench/trace.py``'s ``subtract`` walks every interval of its second
argument for each of its first (quadratic), and the readers'
lookahead patterns (``(?=.*a)(?=.*b)``) rescan an op's text from each
position where it does not match; here both are linear."""

from bench import trace as trace_lib


def subtract(a, b) -> list:
    """Parts of union(a) that union(b) does not cover, as
    ``trace_lib.subtract`` gives them, by one merge of the two sorted
    unions."""
    out, b, j = [], trace_lib.union(b), 0
    for s, e in trace_lib.union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def exposed_s(dev, is_mine) -> float:
    """Seconds in which an operation that ``is_mine(name)`` runs on the
    device and no other leaf operation does (a loop or call that holds it
    hides nothing)."""
    mine = [(s, e) for n, s, e in dev.ops if is_mine(n)]
    if not mine:
        return 0.0
    other = [(s, e) for n, s, e in trace_lib.leaf_ops(dev) if not is_mine(n)]
    return trace_lib.length(subtract(mine, other))


def having(*parts):
    """A test of an op's text: it holds every one of ``parts``."""
    return lambda name: all(p in name for p in parts)


def runs(dev, test) -> tuple:
    """(number of runs, summed device seconds) of the operations that pass
    ``test``."""
    n, t = 0, 0.0
    for name, s, e in dev.ops:
        if test(name):
            n += 1
            t += e - s
    return n, t
