"""Local-training tokens of all silos per second of the window: all the
rounds' tokens over the time from the window's start to the end of its
last round."""


def read(record):
    tokens = record.window.total("tokens")
    return tokens / record.window.window_s if tokens else None
