"""Graph500 rate in millions of traversed edges per second: the edges of
each completed search's component, counted once, summed over the window
(first submit to last result on the host)."""


def read(run):
    edges = run.total("traversed_edges")
    return None if edges is None else edges / run.window.seconds / 1e6
