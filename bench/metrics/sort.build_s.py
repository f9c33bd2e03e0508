"""``sort.build_s``: seconds the run's sort calls spent building the program
(trace, lowering, and compile or load from the persistent cache): the sum
of the program's ``sort.dispatch`` spans marked ``build``, which the
``sort.builds`` counter moved in; with the program's tracer on from before
the sort is made, that is the warm-up's first call.  A run without these
spans reads nothing."""


def read(run):
    tracer = run.data.get("tracer")
    if tracer is None:
        return None
    dispatch = [r for r in tracer[1]
                if r["kind"] == "span" and r["name"] == "sort.dispatch"]
    if not dispatch:
        return None
    return sum(r["dur"] for r in dispatch if r["args"].get("build")) / 1e6
