"""readback_p99_ms.fp: the fingerprint store's readback (span
``drl/store.readback``: the wait for the device, the transfer, the unpack
of verdicts and probe results; runtime/fp_store.py), read from the same
``store_readback`` stage as ``readback_p99_ms.bulk``."""


def read(run):
    return run.cell.reader("readback_p99_ms.bulk")(run)
