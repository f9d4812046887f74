"""Requests to the backend compiler in the traced fit: real compiles
(``jit.backend_compile``) plus those the persistent cache answered
(``jit.cache_read``). A steady fit should make none."""
from _spans import COMPILES, records


def read(m):
    recs = records(m)
    if recs is None:
        return None
    return sum(r["name"] in COMPILES for r in recs)
