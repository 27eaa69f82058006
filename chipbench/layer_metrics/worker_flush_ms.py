"""Worker and service: the worker's ``crypto.flush`` timer per flush
(``stats`` op before and after the traced flushes)."""


def read(obs):
    if not obs["worker_flushes"]:
        return None
    return obs["worker_flush_s"] / obs["worker_flushes"] * 1e3
