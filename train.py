"""Training entry point (reference train.py:5-7):
    python train.py -f config/decima_tpch.yaml
"""

from sparksched_tpu.config import enable_compilation_cache, load
from sparksched_tpu.trainers import make_trainer

if __name__ == "__main__":
    enable_compilation_cache()
    cfg = load()
    trainer = make_trainer(cfg)
    trainer.train()
