"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
program's warm-up and before the reference runs, in GB (1e9). It moves no
end-to-end metric by itself; it sizes cells (the floor is a quarter of a
chip) and shows what a change costs in memory."""


def read(run):
    return run.memory_peak_bytes / 1e9
