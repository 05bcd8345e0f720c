"""Device trace: time in events of the Pallas paged-attention kernel
(``_paged_kernel`` in ``serve/paged_attention.py``) over device busy time."""


def read(run):
    if not run.traced:
        return None
    kernel = sum(s for name, s in run.traced["by_name"].items()
                 if "paged" in name.lower())
    return 100.0 * kernel / run.traced["busy_s"]
