"""The all-to-all's share of its link roofline: the least time the chips'
inter-chip links could take to move the bytes the exchange must move
(``benchlib/exchange.py``, from the catalog and the block layout, at every
chip's published ICI bandwidth at once) over the all-to-all's device time
per chip (trace)."""
from benchlib import exchange, layers


def read(ctx):
    out = ctx["outcome"]
    seconds = layers.device_seconds(ctx["trace"], exchange.ALL_TO_ALL)
    if seconds is None or ctx["peak"] is None:
        return None
    chips = out.layer["chips"]
    least_s = (sum(out.layer["exchange_least_bytes"])
               / (chips * exchange.ici_bytes_per_s(out.layer["device_kind"])))
    return 100.0 * least_s / (seconds / chips)
