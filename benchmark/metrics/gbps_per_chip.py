"""Logical input bytes of every call completed in the window, over the
window's seconds (first call's start to the last call's end), per chip.
Each call counts its input array once, whatever statistics it computes."""


def read(run):
    done = sum(1 for _s, _e, ok in run.steps if ok)
    return done * run.input_bytes / run.window_s / run.cell.chips / 1e9
