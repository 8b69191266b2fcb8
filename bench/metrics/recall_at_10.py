"""Mean recall@10 of the window's answers against the exact reference."""


def read(rec):
    return rec.recall
