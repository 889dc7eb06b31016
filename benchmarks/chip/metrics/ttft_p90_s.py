"""p90 of seconds from due to first token (submit + the program's ttft_wall) over every request due in the window; one not delivered by the end of serving counts at that end."""
from chipbench import readers


def read(ctx):
    return readers.p90(ctx["first_token"])
