"""Operations and bytes of one training epoch, from its shapes: the least
any implementation of Algorithm 1 has to do, whatever runs it.

Per rated event (K = factor width): read the event (16 B), gather u, p, q
and write u, q (20K B); per receiver of its message (itself included)
read the receiver id and weight (8 B) and read-modify-write its p row
(8K B); with DP, write and read the noise row (8K B). Operations per
event: v = p + q, the residual and loss (3K + 5), the three gradients
(9K), the two SGD updates (4K), DP clip and noise (4K); per receiver the
weighted add (2K). The Gaussian draws themselves are not counted.
"""


def epoch_work(x) -> tuple[float, float]:
    K, ev, rcv = x["dim"], x["events_per_epoch"], x["receivers_per_epoch"]
    flops = ev * (16 * K + 5 + (4 * K if x["dp"] else 0)) + rcv * 2 * K
    nbytes = ev * (16 + 20 * K + (8 * K if x["dp"] else 0)) + rcv * (8 * K + 8)
    return float(flops), float(nbytes)
