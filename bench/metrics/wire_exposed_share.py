"""wire_exposed_share: share of the traced window in which a device runs
the stage boundary's collective-permute and no other operation, averaged
over the devices.  The boundary's ops are the ``collective-permute``
instructions (and the ``-start`` and ``-done`` halves of asynchronous
ones) of the programs the window ran (``Summary.hlo_texts``): in the
pipeline's program the only collective-permutes are the wire's, the
code going forward and the gradient coming back.  An op that only
encloses others (``while``, ``conditional``, ``call``) is not another
operation.  Moves ``train_tokens_per_s``.  Nothing to read, nothing
returned."""
import re

LAYER = "pipeline wire"
MOVES = "train_tokens_per_s"
UNIT = "%"
SOURCE = "device_trace"
WIRE = ("collective-permute", "collective-permute-start",
        "collective-permute-done")
ENCLOSING = ("while", "conditional", "call")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def opcodes(texts):
    """{(program, instruction): opcode} of every instruction."""
    out = {}
    for text in texts:
        m = re.match(r"HloModule ([^\s,]+)", text)
        module = m.group(1) if m else ""
        for line in text.splitlines():
            m = _LINE.match(line)
            if not m:
                continue
            op = _OPCODE.search(" " + m.group(2))
            if op:
                out[(module, m.group(1))] = op.group(1)
    return out


def _union(ivs):
    out = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _less(ivs, cover):
    """Length of the union ``ivs`` outside the union ``cover``."""
    total, j = 0, 0
    for s, e in ivs:
        t = s
        while j < len(cover) and cover[j][1] <= t:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > t:
                total += cover[k][0] - t
            t = max(t, cover[k][1])
            k += 1
        if t < e:
            total += e - t
    return total


def read(ctx):
    from bench.harness import trace as T

    s = ctx.summary
    code = opcodes(s.hlo_texts)
    wire, other = {}, {}
    for o in s.ops:
        op = code.get((T.module_name(o.module), o.name))
        if op in ENCLOSING:
            continue
        (wire if op in WIRE else other).setdefault(o.device, []).append(
            (o.start, o.end))
    if not wire:
        return None
    exposed = sum(_less(_union(ivs), _union(other.get(d, [])))
                  for d, ivs in wire.items())
    return 100.0 * exposed / 1e9 / s.n_devices / s.window_s
