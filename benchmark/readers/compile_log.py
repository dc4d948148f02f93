"""One number out of the program's compile log
(`paddle_tpu/core/compile_cache.py::compile_log`: one record a compiled
XLA program, with the seconds of its trace, its lowering and its
executable, the compile cache's verdict, and when it was in hand on the
`time.perf_counter` clock the window's stamps are on).

The metric's file gives `field` (a key of the log's `summary()`, or
several joined by `+`), `programs` (regular expressions on a record's
name; every program without it) and `when`: `all`, the whole process, for
which the programs have to be told by name (both runners compile their
plain reference in this process after the window), or `window`, the
records whose `t` lies inside `obs["window"]`, whose names go to standard
output. A program from before the log, a process that installed no
listener and a train run asked for its window read nothing."""


def read(spec, obs):
    try:
        from paddle_tpu.core.compile_cache import compile_log
    except ImportError:
        return None
    if not compile_log.records():
        return None
    span = {}
    if spec["when"] == "window":
        if not obs.get("window"):
            return None
        span = dict(zip(("since", "until"), obs["window"]))
        names = [r["name"] for r in
                 compile_log.records(spec.get("programs"), **span)]
        if names:
            print(f"{spec['name']}: compiled inside the window: "
                  + ", ".join(names))
    got = compile_log.summary(spec.get("programs"), **span)
    if spec["when"] == "all" and not got["count"]:
        return None
    return sum(got[f] for f in spec["field"].split("+"))
