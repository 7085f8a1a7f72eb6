"""recovery_ms: the mean over the faults injected in the window of the time
from the injection to the end of the first step, after the fault was
detected, in which the request it hit committed a further token (or was
answered), in ms."""


def read(art):
    done = [f for f in art["run"].faults if f.t_heal is not None]
    if not done:
        return None
    return sum(f.t_heal - f.t_inj for f in done) / len(done) * 1e3
