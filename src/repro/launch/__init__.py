from .mesh import make_local_mesh, make_query_mesh

__all__ = ["make_local_mesh", "make_query_mesh"]
