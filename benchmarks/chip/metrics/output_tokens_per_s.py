"""Generated tokens of every task finished in the window over the window's
length (host clock, from the first task's send to the last one's end)."""


def read(run):
    return sum(d.tokens.size for d in run.done) / run.elapsed
