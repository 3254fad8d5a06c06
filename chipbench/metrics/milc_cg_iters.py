"""Mean CG iterations per solve in the window, as the program counts them
(``CGResult.iterations``).  Program counter."""


def read(run):
    its = run["record"].get("iterations")
    if not its:
        return None
    return sum(its) / len(its)
