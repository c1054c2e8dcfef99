"""fp32 gradient bytes per chip merged per second over the whole window:
every completed round's bytes, over the time from the window's start to
the completion of the last round begun in it."""
import lib


def read(data):
    return lib.window_GBps(data)
