"""Share of the rows the engine computed that carried decode or prefill
work (the instances' row counters), in %."""


def read(r):
    if not r["row_slots_total"]:
        return None
    return 100.0 * r["row_slots_active"] / r["row_slots_total"]
