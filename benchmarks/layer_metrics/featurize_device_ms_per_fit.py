"""Device time inside the cosine-feature program (``jit_cosine_features``,
one run per bank), per traced fit. A program that still calls it
``jit__lambda`` gives None."""


def read(m):
    t = m["trace"]
    fits = m["facts"].get("traced_fits")
    if not t or not fits or "jit_cosine_features" not in t["programs_s"]:
        return None
    return 1e3 * t["programs_s"]["jit_cosine_features"] / fits
