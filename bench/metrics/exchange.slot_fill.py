"""Share of the key-word slots the all-to-all exchange ships that carry an
item.

Items are the program's `sent_words` counter, summed over the chips and
over the window's jobs. Slots are the elements of the `u32` key lanes of
the window's `all-to-all` ops, read from the result shapes in each op's
HLO text and summed over the chips; a lane of `s32` counts is not a key
lane and is left out. Each destination's tile is shipped at its planned
capacity whatever it holds, so this prices the padded slots.
"""

import re

from bench import scopes, trace as xt

_U32 = re.compile(r"\bu32\[([\d,]*)\]")


def key_slots(hlo_text: str) -> int:
    """Elements of the `u32` results of one op's HLO text, if the op is an
    `all-to-all`; else 0."""
    head, sep, _ = hlo_text.partition(" all-to-all(")
    if not sep or "=" not in head:
        return 0
    total = 0
    for dims in _U32.findall(head.split("=", 1)[1]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def read(ctx):
    layers = scopes.of(ctx)
    if layers is None or not ctx.counters.get("sent_words"):
        return None
    lo, hi = layers.window
    raw = xt.load(xt.find_xplane(scopes.TRACE_DIR))
    slots = sum(key_slots(name) for dev in raw.devices
                for name, s, e in dev.ops if s < hi and e > lo)
    if slots == 0:
        return None
    return 100.0 * ctx.counters["sent_words"] / slots
