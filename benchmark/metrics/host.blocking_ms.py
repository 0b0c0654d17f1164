"""Median over the traced calls of the chip's idle time under an open
``flox.*`` host span, mean over the chips, each chip's clock aligned by its
program runs: the time the program's host code holds the chip."""


def read(run):
    from benchmark import program_trace

    if run.trace is None or run.trace.program is None:
        return None
    return program_trace.blocking_ms(*program_trace.align(run.trace, run.trace.program))
