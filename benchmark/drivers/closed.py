"""Driver ``closed``: as many clients as the traffic names, each sending its
next request when its last one ends. The loop runs ``warm_seconds`` before
the window opens, so that the slots are already out of step, after
``warm_requests`` short untimed requests have run to their end. At the
window's end the requests in flight are cut: they are neither attempted nor
failed, and ``correct`` samples those that finished inside the window."""
from __future__ import annotations

import threading
import time

from .. import traffic as traffic_mod
from . import serving


def run(cell, args, ctx):
    served = serving.Served(cell, args, ctx)
    tr, cfg = served.tr, served.cfg
    per_client = traffic_mod.closed_requests(
        tr, args.seed, cfg["vocab_size"], count=tr["requests_per_client"])
    served.warm_up(traffic_mod.closed_requests(
        dict(tr, clients=1, output_len=tr["warm_output_len"]), args.seed + 1,
        cfg["vocab_size"], count=tr["warm_requests"])[0])
    stop = threading.Event()

    def client(requests):
        for request in requests:
            if stop.is_set():
                return
            served.send(request, due=time.perf_counter(), timed=True)

    clients = [threading.Thread(target=client, args=(reqs,),
                                name=f"bench-client-{i}", daemon=True)
               for i, reqs in enumerate(per_client)]
    for t in clients:
        t.start()
    time.sleep(tr["warm_seconds"])
    t0 = served.open_window()
    served.sleep_until(t0 + args.seconds)
    stop.set()
    run = served.finish_window(clients, cut=True)
    served.shutdown()
    run["end_to_end"] = {
        "serve_tok_s": run["tokens_in_window"] / run["window_s"]}
    done = [r for r in run["records"] if r["finished"]]
    print(f"closed loop: {run['tokens_in_window']} tokens in the window, "
          f"{len(done)} of {run['attempted']} requests finished", flush=True)
    run["numbers"] = served.compare(run)
    return run
