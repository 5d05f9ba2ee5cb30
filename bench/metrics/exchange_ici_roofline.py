"""The all-to-all exchange's share of its ICI roofline.

Bytes are the least one chip must send. Each item that reaches a store is
one 4-byte key word; the items are the program's `sent_words` counter,
summed over the chips and over the window's jobs. Under the uniform owner
hash a share (P - 1) / P of a chip's items belong to another chip, so one
chip sends `sent_words * 4 * (P - 1) / P**2` bytes, P the chips in the
trace. This counts the algorithm's bytes, not the padded tiles the
collective ships, so a change that stops shipping sentinel slots moves
this share. Time is the device time per chip of the `exchange` scope in
`local_update`; the bound is one chip's ICI bandwidth (bench/peaks.json).
"""

from bench import scopes

KEY_BYTES = 4


def exchange_bytes(sent_words: int, chips: int) -> float:
    """Least bytes one of `chips` chips sends for `sent_words` items
    summed over all chips."""
    return KEY_BYTES * int(sent_words) * (chips - 1) / chips ** 2


def read(ctx):
    layers = scopes.of(ctx)
    if layers is None or not ctx.counters.get("sent_words"):
        return None
    t = layers.path_s("exchange", "local_update")
    if t <= 0:
        return None
    least = exchange_bytes(ctx.counters["sent_words"],
                           ctx.trace.n_devices) / (
        ctx.peaks["ici_bits_per_s"] / 8)
    return 100.0 * least / t
