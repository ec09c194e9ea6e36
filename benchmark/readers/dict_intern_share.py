"""Local executor: the share of the string columns converted from Arrow
whose dictionary came out of the intern table (columnar/arrow_interop.py
DICTIONARIES), so that the programs bound to it hit the op cache: the
dicts_interned over the strings attributes of the arrow.convert spans
under execute, summed over the window's answered statements, in %.
Nothing where no such span carries dicts_interned (a program from
before the count) or none converted a string column."""


def _under_execute(p):
    by_id = {s.span_id: s for s in p.spans}
    for s in p.spans:
        if s.name != "arrow.convert":
            continue
        parent = by_id.get(s.parent_id)
        while parent is not None and parent.name != "execute":
            parent = by_id.get(parent.parent_id)
        if parent is not None:
            yield s.attributes


def read(run):
    interned = strings = 0
    for st in run.done:
        if st.profile is None or not hasattr(st.profile, "spans"):
            continue
        for attrs in _under_execute(st.profile):
            if "dicts_interned" in attrs:
                interned += attrs["dicts_interned"]
                strings += attrs["strings"]
    if strings == 0:
        return None
    return 100.0 * interned / strings
