"""Share, in %, of the HBM roofline that the SpMV program reaches: the
paper's useful bytes over peak HBM bandwidth, over the device time of the
served program's XLA module in the traced window."""
from bench.roofline import share


def read(run):
    return share(run, "useful_bytes")
