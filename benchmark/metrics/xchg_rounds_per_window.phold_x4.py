"""All_to_all rounds of the cross-shard exchange per window (engine
counters, window deltas); nothing where the family does not count them."""


def read(rec: dict) -> float | None:
    c = rec["counters"]
    if "n_xchg_rounds" not in c or not c["n_windows"]:
        return None
    return c["n_xchg_rounds"] / c["n_windows"]
