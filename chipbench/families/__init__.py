"""One module per model family: build(config) -> the program's config
object, the operation and byte counts, and the plain float32 reference."""
