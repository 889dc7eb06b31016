"""Share of the page tables that the paged attention kernel walks at
decode, in %: the table pages the decode steps' rows hold
(`decode_pages`, per layer and kv head) over every table page of every
slot in those steps (steps x slots x ceil(max_len / page_size)). The
kernel DMAs and computes over a row's live pages alone, and an idle
slot holds none. A program without the counter reads None."""
from chipbench import counters


def read(ctx):
    pages = counters.delta(ctx, "decode_pages")
    steps = counters.delta(ctx, "steps")
    if pages is None or not steps:
        return None
    s = ctx["config"]["serving"]
    table = -(-s["max_len"] // s["page_size"])
    return 100.0 * pages / (steps * ctx["slots"] * table)
