"""Output tokens streamed inside the window over its seconds."""


def read(rec):
    w = rec.window
    n = rec.tokens_in_window()
    return n / (w.close_s - w.open_s) if n else None
