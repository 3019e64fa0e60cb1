"""Client and server (serve/server.py): mean per window job of its
``server.queue`` span, from submit to dispatch onto a session slot."""
import jobspans


def read(run):
    return jobspans.mean_per_job(
        run, lambda root, spans: jobspans.durations(spans, "server.queue"))
