"""Largest part's out-degree load over |E|/k after S supersteps, as the
program reports it; median over the window's jobs."""
import statistics


def read(rec):
    return statistics.median(j["max_norm_load"] for j in rec["jobs"])
