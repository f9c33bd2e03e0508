"""``sched.prefix_hit_pct``: prompt tokens spliced from the KV pool over all
prompt tokens of the window's requests.  The spliced pages are the ones the
server records in its ``serve.attach`` events (the program's tracer, on in
the traced run), times the page size; a run without the tracer reads
nothing."""


def read(run):
    if "tracer" not in run.data:
        return None
    epoch, records = run.data["tracer"]
    t0 = run.data["window"][0]
    pages = sum(r["args"].get("pages", 0) for r in records
                if r["kind"] == "event" and r["name"] == "serve.attach"
                and epoch + r["ts"] / 1e6 >= t0)
    prompt = sum(r["plen"] for r in run.data["requests"])
    if not prompt:
        return None
    return 100.0 * pages * run.data["page_size"] / prompt
