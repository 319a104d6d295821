from .cli import main
from .runtime import enable_compile_cache

enable_compile_cache()
main()
