"""`slots_per_superstep.mesh4`: neighbour slots a worker gathers per
field per superstep on the mesh (rows a worker times the columns it
gathers), from the program's layout counter
(`repro.runtime.spmd.last_mesh_layout`) as the driver copied it."""


def read(run):
    return run.counters.get("slots_per_superstep")
