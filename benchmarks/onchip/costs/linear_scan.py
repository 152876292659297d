"""Operations and bytes the minGRU ``linear_scan`` needs for one prefill
call (every layer of one chunk): h_t = a_t * h_{t-1} + b_t over the
``tokens`` valid positions (grid padding is not work) and ``width``
channels, reading a and b and writing h once, in ``act_bytes`` each, plus
the carried state in and out:

    flops = layers * tokens * width * 2
    bytes = layers * (tokens * width * 3 * act_bytes
                      + rows * width * 2 * act_bytes)
"""


def cost(*, tokens, rows, width, n_layers, act_bytes=2):
    flops = n_layers * tokens * width * 2
    nbytes = n_layers * (tokens * width * 3 * act_bytes
                         + rows * width * 2 * act_bytes)
    return float(flops), float(nbytes)
