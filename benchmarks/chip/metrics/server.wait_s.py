"""Client/server layer (serve/server.py, serve/client.py): mean over the
window's iterations of the client's latency minus the job's run time —
queueing, dispatch and the wait RPC."""


def read(run):
    its = run["iterations"]
    if not its:
        return None
    return sum(i["latency_s"] - i["run_seconds"] for i in its) / len(its)
