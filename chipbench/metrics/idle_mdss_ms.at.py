"""Device idle milliseconds per AT iteration while the latest-started
``emerald:`` span open on any host thread is MDSS staging (``ship``,
``install``, ``d2h`` or ``hash``). The four ``idle_*`` metrics split the
idle time of ``device_idle.at``. Moves ``at_iter_s``."""
from chipbench.host_spans import idle_ms


def read(obs):
    return idle_ms(obs, "mdss")
