"""Output tokens committed per row that carried work: what grouped
speculation adds to each decode row's one token."""


def read(r):
    if not r["row_slots_active"]:
        return None
    return r["tokens"] / r["row_slots_active"]
