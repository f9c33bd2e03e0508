"""The program's own sort spans in a traced run, grouped by the call id that
every span of one ``engine.sort`` call carries.  A system module that
installs the program's tracer hands over its epoch on the ``perf_counter``
clock and its records as ``run.data["tracer"]``; without them the readers
read nothing."""

#: the host-path children of an ``engine.sort`` span, in call order
PARTS = ("sort.prepare", "sort.dispatch", "sort.unpad")


def sort_calls(records) -> dict:
    """call id -> {span name: record} of each ``engine.sort`` span and its
    `PARTS`."""
    out: dict = {}
    for r in records:
        if r["kind"] == "span" and (r["name"] == "engine.sort"
                                    or r["name"] in PARTS):
            out.setdefault(r["args"]["call"], {})[r["name"]] = r
    return out
