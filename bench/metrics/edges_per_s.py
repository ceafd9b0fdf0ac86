"""Input |E| times the supersteps of every completed job, over the window
(first job's start to last job's end), on the host clock."""


def read(rec):
    jobs = rec["jobs"]
    elapsed = jobs[-1]["end"] - jobs[0]["start"]
    return rec["m"] * sum(j["steps"] for j in jobs) / elapsed
