"""store_host_p99_ms.fp: the fingerprint store's host section (the hashing
pass, span ``drl/store.hash``, then the store-lock section, span
``drl/store.dispatch``; runtime/fp_store.py), read from the same
``store_host`` stage as ``store_host_p99_ms.bulk``."""


def read(run):
    return run.cell.reader("store_host_p99_ms.bulk")(run)
