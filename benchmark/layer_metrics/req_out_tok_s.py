"""scheduler: output tokens received inside the window over its length, in a
cell that does not judge out_tok_s (an open loop offers a fixed rate, so this
is the offered load unless requests fail). Source: host_clock."""


def read(ctx):
    return ctx["summary"].get("out_tok_s")
