"""Rows whose results were complete inside the window, over the window
(host clock). Every cell."""


def read(run):
    return run.window.rows_done / run.window.seconds
