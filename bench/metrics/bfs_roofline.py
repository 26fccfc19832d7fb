"""Share, in %, of the HBM roofline that the BFS program reaches: the
searches' useful bytes (each component entry read once, each reached
vertex's offset read and parent written) over peak HBM bandwidth, over the
device time of the XLA modules in the traced window."""
from bench.roofline import share


def read(run):
    return share(run, "useful_bytes")
