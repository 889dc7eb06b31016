"""p90 of seconds a request due in the window waited between submit and
first token beyond its own admission compute (ttft_wall - ttft): the queue
and the stalls behind other requests' chunks and bursts."""
from chipbench import readers, stats


def read(ctx):
    w0, w1 = ctx["window"]
    return readers.p90(r["ttft_wall"] - r["ttft"] for r in ctx["records"]
                       if r["done"] is not None and r["status"] == "completed"
                       and stats.in_window(r["due"], w0, w1))
