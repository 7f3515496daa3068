"""Kilobytes both ways on the volunteers' sockets, over every call made in
the window, per commit received in it."""


def read(run):
    commits = run.window_commits
    if not commits:
        return None
    moved = sum(c.sent + c.received for t in run.tickets for c in t.calls
                if run.in_window(c.t0))
    return moved / commits / 1e3
