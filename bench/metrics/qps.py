"""Queries completed inside the window over the window's length."""


def read(run):
    return len(run.window.completed_in_window()) / run.window.seconds
