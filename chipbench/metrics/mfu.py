"""The whole step's share of the chip's op peak, for every
``mfu.<cell kind>`` metric.

Model ops of the rows that the traced window completed over window
seconds x chips x the int8 op peak (``work.mfu_pct``).  It bounds every
kernel's share: a kernel taken off the path leaves its roofline silent,
and this still counts the whole step.
"""

from chipbench import work


def read(rec):
    return work.mfu_pct(rec)
