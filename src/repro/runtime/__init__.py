from repro.runtime.train import Trainer, TrainConfig, FaultInjector
