"""Distribution substrate: mesh context, sharding rules, collectives."""
from repro.distributed.ctx import current_mesh, use_mesh, wsc, batch_axes
