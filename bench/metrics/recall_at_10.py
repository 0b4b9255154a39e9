"""Mean recall@10 of the window's answers against exact brute force."""


def read(run):
    return run.recall
