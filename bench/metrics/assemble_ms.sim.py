"""Result assembly per call: from the call's last device operation to its
span end, averaged over the traced calls (ms)."""


def read(ctx):
    tail = [c["assemble_ns"] for c in ctx["trace"]["calls"]
            if c["assemble_ns"] is not None]
    return sum(tail) / len(tail) / 1e6 if tail else None
