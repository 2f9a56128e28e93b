"""Real frames per dispatched batch slot: frames answered in the window
over (dispatches in the window x batch width). Dispatches are the
backend's host-sync count, one per dispatched batch."""


def read(run):
    if not run.dispatches:
        return None
    return float(run.served_in_window().sum()) / (run.dispatches * run.width)
