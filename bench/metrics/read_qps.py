"""Reads answered per second: every read sent in the window and answered,
over the seconds from the window's opening to the last of those answers."""


def read(w):
    return len(w.reads) / w.seconds if w.reads else None
