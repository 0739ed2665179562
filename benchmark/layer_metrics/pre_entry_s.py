"""Seconds from the start of the process to the entry of the program's
``main``, from the first ``boot`` event (``t_main`` less ``t_process``;
where ``process_from`` is ``package`` the process's start could not be
read and the package's first line stands in for it): the interpreter,
and under this harness its own imports, the package's, the device start
and the case file.  It says whether a move of ``setup_s`` is the
program's at all.  A program without the event (before PR 51) reads
nothing.  Layer: entry."""


def read(events, device_trace, cell):
    boot = next((e for e in events if e.get("kind") == "boot"), None)
    return None if boot is None else boot["t_main"] - boot["t_process"]
