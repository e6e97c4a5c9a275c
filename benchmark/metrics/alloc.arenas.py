"""How many malloc arenas glibc has made (``malloc_info``'s heaps) at the
end of the last job read: one for the main thread and up to eight a core for
the others; the count is the process's so far and never falls, so the
profiler's own threads are in it. Today it reads the machine's cap (eight a
core, every arena made before the first job) and the program cannot move it;
it is here for the day the program sets a limit (ROADMAP S17). Read at the end
of the window's last job (``threads.py``)."""

import threads


def read(run):
    arenas = threads.alloc_ends(run, "arenas")
    return None if arenas is None else arenas[-1]
