"""speedup_vs_sequential.graph: time per call of the same exported graph
lowered one operator per step into one jax.jit (the paper's sequential
baseline) over the Opara executable's, both timed alike after the window
in the same process (host clock)."""


def read(run):
    if not run.sequential_ms or not run.opara_ms:
        return None
    return run.sequential_ms / run.opara_ms
