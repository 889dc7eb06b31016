"""ms per scheduling round of host work over the window: the seconds of the
scheduler's `serve.poll` span (`poll_s`) less its device-synced children
(`prefill_s`, `decode_s`), over its rounds (`polls`): admission
bookkeeping, harvest transfers and per-token accounting, while the device
waits for the next call."""
from chipbench import counters


def read(ctx):
    return counters.ms_per(ctx, "polls", ("poll_s",),
                           ("prefill_s", "decode_s"))
