"""kernel_roofline.fp: the fingerprint store's decision kernels' share of
the HBM roofline, read exactly as ``kernel_roofline.bulk`` reads the
host-directory kernels': the configuration's least bytes per decision
(``configs/token-bucket-10m-fp.py``: the key's directory cell counts
too) times the window's decisions at the chip's peak HBM bandwidth,
over the device time of the modules named by the configuration's
``decision_modules`` (``fp_acquire``: probe, claim and decide in one
launch)."""


def read(run):
    return run.cell.reader("kernel_roofline.bulk")(run)
