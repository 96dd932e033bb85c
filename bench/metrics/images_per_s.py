"""Images classified per second: every image whose logits reached the
host in the window, over the window's length."""


def read(run):
    return run.window.completed / run.window.seconds
