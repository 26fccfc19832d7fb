"""Paper §5.1 bandwidth in GB/s: useful bytes (value and column of every
nonzero, x and y) summed over the completed products, over the window
(first submit to last result on the host)."""


def read(run):
    useful = run.total("useful_bytes")
    return None if useful is None else useful / run.window.seconds / 1e9
