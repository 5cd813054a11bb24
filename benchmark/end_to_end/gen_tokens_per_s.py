"""Output tokens that reached their caller inside the window, over its
length. Tokens of requests the window cut at either end count where they
arrived, so no request length leaks into the rate."""


def read(run):
    return run.obs["tokens_in_window"] / run.obs["elapsed_s"]
