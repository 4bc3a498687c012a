"""Output tokens committed in the window over the window's host seconds."""


def read(r):
    return r["tokens"] / r["window_s"]
