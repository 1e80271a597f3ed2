"""Launch layer: mesh construction, sharding rules, drivers."""
