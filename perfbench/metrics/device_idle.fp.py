"""device_idle.fp: the share of the traced window in which no operation
ran on a chip, read as ``device_idle.bulk`` reads it."""


def read(run):
    return run.cell.reader("device_idle.bulk")(run)
