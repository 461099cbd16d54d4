"""Cohort clients trained, compressed and aggregated per second of the
window: all the rounds' clients over the window's time."""


def read(record):
    clients = record.window.total("clients")
    return clients / record.window.window_s if clients else None
