"""Open loop: requests completed inside the window over its length."""


def read(run):
    if "completed_in_window" not in run.window:
        return None
    return run.window["completed_in_window"] / run.window["seconds"]
