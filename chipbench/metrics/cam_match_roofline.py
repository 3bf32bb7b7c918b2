"""Share of its roofline that the CAM match kernel reaches, for every
``cam_match_roofline.<cell kind>`` metric: the least time the chip could
take for the model's work of every kernel call in the traced window (per
call the larger of ops over the int8 op peak and bytes over HBM
bandwidth) over the summed device time of the kernel's events
(``work.kernel_roofline_pct``)."""

from chipbench import work

# the device op that runs kernels/cam_match.py's Pallas kernel
KERNEL = "cam_match"


def read(rec):
    return work.kernel_roofline_pct(rec, KERNEL)
