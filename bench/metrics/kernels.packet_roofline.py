"""Roofline share of the sampled Gram-packet kernel (row or column gather):
least time for the packets the algorithm needs over their device time."""
from bench.roofline import share
from bench.work import packet

PATTERN = r"^gram_packet_sampled(_cols)?_pallas\b"


def read(run):
    return share(run, PATTERN, "packet", packet.work)
