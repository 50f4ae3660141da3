"""Device idle milliseconds per AT iteration while no ``emerald:`` span
is open on any host thread: the caller between its calls, threads waking
up. The four ``idle_*`` metrics split the idle time of
``device_idle.at``. Moves ``at_iter_s``."""
from chipbench.host_spans import idle_ms


def read(obs):
    return idle_ms(obs, "unspanned")
