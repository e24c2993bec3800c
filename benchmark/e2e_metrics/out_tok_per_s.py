"""Output tokens the client received inside the window, over its length —
all the work of the window over all of its time."""

from benchmark import stats


def read(collected: dict):
    return stats.rate(collected["window_tokens"], collected["seconds"])
