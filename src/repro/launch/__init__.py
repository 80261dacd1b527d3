"""Launch layer: meshes, sharding plans, step builders, train and serve
drivers."""
