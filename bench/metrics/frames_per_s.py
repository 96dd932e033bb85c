"""Sensor frames classified per second: every frame whose logits reached
the host in the window, over the window's length."""


def read(run):
    return run.window.completed / run.window.seconds
