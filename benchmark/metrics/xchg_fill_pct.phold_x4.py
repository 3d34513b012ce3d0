"""Share of the all_to_all slots moved between distinct chips that carried
an event, in percent: events sent across chips over the slots their
rounds moved (engine counters, window deltas); nothing where the family
does not count them."""


def read(rec: dict) -> float | None:
    c = rec["counters"]
    if not c.get("xchg_slots"):
        return None
    return 100.0 * c["n_cross_shard"] / c["xchg_slots"]
