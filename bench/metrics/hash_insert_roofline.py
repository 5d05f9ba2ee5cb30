"""The receiver insert's share of its HBM roofline.

Bytes are what the algorithm must move per item that reaches the count
store: the item's (word, count) pair read (8 B), and one 8 B slot (key and
count) read and written (16 B): 24 B per item. Items reaching the store
are the program's `sent_words` counter, summed over the window's jobs.
This counts the algorithm's work, not the kernel's per-call copy of the
whole table, so a kernel that stops copying the table moves this share.
The bound is HBM bandwidth: the kernel does no arithmetic to speak of.
Time is the summed device time of the `hash_insert` kernel's events.
"""

BYTES_PER_ITEM = 24


def insert_bytes(items: int) -> int:
    return BYTES_PER_ITEM * int(items)


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s("hash_insert")
    if t <= 0 or not ctx.counters.get("sent_words"):
        return None
    least = insert_bytes(ctx.counters["sent_words"]) / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / t
