"""The one general load generator. A traffic mix is a data file,
`benchmark/traffic/<mix>.json`: a list of clients, each a closed loop on
its own thread and its own connection.

  query_stream    the named statements of the configuration's data set,
                  pass after pass; `order` is `fixed` or
                  `seeded_permutation` (a new permutation each pass,
                  drawn from the seed: the spec's stream ordering); no
                  pass begins after the deadline, the last one is
                  finished

A closed loop sends its next request when the last one is answered: one
analyst's session. Everything a client sends is a function of the seed.
The window's records are what the end-to-end metrics and the comparison
are computed from; nothing is computed here.

Warning 9013 (a device dispatch that degraded): the server sends no
warning count with a result set, so seeing one costs a SHOW WARNINGS
round trip. Every warm-up statement pays it; in the window only each
client's last statement does, after its answer is timed, and the
`device_fallback` counter covers the rest: the program appends the
warning in one place, which bumps that counter first
(`utils/device_guard._note_fallback`).
"""
import contextlib
import threading
import time

import numpy as np

from wire import Wire, WireError

DEGRADED = "9013"


class Execution:
    """One query of the window."""
    __slots__ = ("client", "name", "index", "t_send", "t_done", "rows",
                 "error", "warnings")

    def __init__(self, client, name, index):
        self.client, self.name, self.index = client, name, index
        self.t_send = self.t_done = None
        self.rows = self.error = None
        self.warnings = []


def _annotate(trace, label):
    if not trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(label)


class Client(threading.Thread):
    def __init__(self, spec, port, dataset, seed, trace):
        super().__init__(name=f"client-{spec['name']}", daemon=True)
        self.spec, self.dataset, self.trace = spec, dataset, trace
        self.rng = np.random.default_rng([int(seed), 79])
        self.wire = Wire(port)
        self.records = []
        self.deadline = self.t0 = 0.0
        self.crash = None
        self.announce = None        # called with each request's name
        self.ask_warnings = False   # SHOW WARNINGS after every statement
        if spec["kind"] != "query_stream":
            raise SystemExit(f"traffic: unknown client kind {spec['kind']!r}")
        if spec.get("order", "fixed") not in ("fixed", "seeded_permutation"):
            raise SystemExit(f"traffic: unknown order {spec['order']!r}")
        for s in spec["statements"]:
            if s not in dataset.STATEMENTS:
                raise SystemExit(f"traffic: unknown statement {s!r}")

    def query(self, name):
        ex = Execution(self.spec["name"], name, len(self.records))
        if self.announce:
            self.announce(name)
        with _annotate(self.trace, f"stmt:{name}"):
            ex.t_send = time.perf_counter()
            try:
                ex.rows = self.wire.rows(self.dataset.STATEMENTS[name])
            except WireError as e:
                ex.error = str(e)
            ex.t_done = time.perf_counter()
        if self.ask_warnings:
            self.warnings_of(ex)
        self.records.append(ex)
        return ex

    def warnings_of(self, ex):
        ex.warnings = [w for w in self.wire.rows("show warnings")
                       if w[1] == DEGRADED]

    def one_pass(self):
        """One whole pass of the statements: a pass begun before the
        deadline is finished, so every window holds whole passes and the
        same mix of statements under every seed. -> False once the
        deadline has passed."""
        names = list(self.spec["statements"])
        if self.spec.get("order") == "seeded_permutation":
            names = [names[i] for i in self.rng.permutation(len(names))]
        for name in names:
            self.query(name)
        return time.perf_counter() < self.deadline

    def run(self):
        try:
            while self.one_pass():
                pass
            self.warnings_of(self.records[-1])
        except Exception as e:                      # noqa: BLE001
            # a thread's exception is read by the harness after join
            self.crash = e

    def close(self):
        self.wire.close()
