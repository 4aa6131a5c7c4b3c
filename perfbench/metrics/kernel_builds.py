"""Kernel builds per query of the window: the program's ``kernel.retrace``
counter (a new kernel traced into the kernel cache) over the window's
queries. 0 when set-up built every kernel the window runs; a kernel keyed on
a literal builds again for every key that has left the cache."""


def read(run):
    return run.counter("kernel.retrace") / run.n if run.n else None
