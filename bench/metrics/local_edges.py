"""Share of directed edges inside one part after S supersteps, as the
program reports it; median over the window's jobs."""
import statistics


def read(rec):
    return statistics.median(j["local_edges"] for j in rec["jobs"])
