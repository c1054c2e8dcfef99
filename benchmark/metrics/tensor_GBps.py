"""Tensor bytes delivered and verified per second over the whole window:
every acknowledged push's bytes, over the time from the window's start to
the completion of the last push issued in it."""
import lib


def read(data):
    return lib.window_GBps(data)
